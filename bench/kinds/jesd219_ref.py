"""The plain reference of JESD219A's enterprise workload on the aged
drive: sector-addressed writes through the benchmark's page-mapped FTL
(``reference.PlainFTL``, aged by ``reference.aged_ftl``) with
read-modify-write, then the benchmark's event loop.  It imports
nothing of the program.

A write covers the pages from its first sector's to its last's.  A page
it covers only in part, whose logical page is mapped, is read first
(one ``FTL_READ``, no host payload, owned by the request); then every
page is written as ``PlainFTL.write`` writes it, GC and all.
"""

from __future__ import annotations

import numpy as np

import reference
from reference import FTL_READ, GC_READ


def sectors_per_page(cfg: dict) -> int:
    page = cfg["cell"][cfg["cells"][0]]["page_data_bytes"]
    return page // cfg["sector_bytes"]


def translate(ftl: reference.PlainFTL, start, n_sectors, spp: int):
    """The requests through ``ftl``: (op classes, the request of each
    op, GC flags, RMW-read flags, number of RMW reads)."""
    out_cls: list[int] = []
    out_req: list[int] = []
    rmw_at: list[int] = []
    for i, (s, n) in enumerate(zip(np.asarray(start).tolist(),
                                   np.asarray(n_sectors).tolist())):
        def emit(c, i=i):
            out_cls.append(c)
            out_req.append(i)
        end = s + n
        for page in range(s // spp, -(-end // spp)):
            whole = s <= page * spp and (page + 1) * spp <= end
            if not whole and ftl.l2p[page] >= 0:
                rmw_at.append(len(out_cls))
                emit(FTL_READ)
            ftl.write(page, emit)
    cls = np.asarray(out_cls, np.int64)
    rmw = np.zeros(len(cls), bool)
    rmw[rmw_at] = True
    return cls, np.asarray(out_req, np.int64), cls >= GC_READ, rmw


def simulate_one(tab: dict, cls, channels: int, ways: int, arrival,
                 dtype=np.float64):
    """(end time, completion of each op) of one design point, the ops
    striped over channels, then ways: ``reference.simulate``'s
    recurrence.  In float64 it runs as plain Python floats, the same
    sums in the same order, which keeps a check of millions of ops to
    seconds; any other precision goes through ``reference.simulate``."""
    cls = np.asarray(cls, np.int64)
    place = reference.stripe(len(cls), channels, ways)
    if dtype is not np.float64:
        end, done = reference.simulate(tab, cls, *place, channels, ways,
                                       arrival=arrival, dtype=dtype,
                                       completions=True)
        return float(end[0]), done[0].astype(np.float64)
    chan, way, par = (np.asarray(a, np.int64) for a in place)
    cmdpre = (tab["cmd"][0, cls] + tab["pre"][0, cls]).tolist()
    slot = tab["slot"][0, cls].tolist()
    ctrl = tab["ctrl"][0, cls].tolist()
    arb = tab["arb"][0, cls].tolist()
    post = np.where(par % 2 == 1, tab["post_hi"][0, cls],
                    tab["post_lo"][0, cls]).tolist()
    arr = np.asarray(arrival, np.float64).tolist()
    cs, ks = chan.tolist(), (chan * ways + way).tolist()
    bus = [0.0] * channels
    chip = [0.0] * (channels * ways)
    ctrl_free = 0.0
    done = [0.0] * len(cls)
    for t in range(len(cls)):
        c, k = cs[t], ks[t]
        ready = max(chip[k], arr[t]) + cmdpre[t]
        start = max(max(bus[c], ready), ctrl_free) + arb[t]
        b = start + slot[t]
        bus[c] = b
        ctrl_free = start + ctrl[t]
        chip[k] = b + post[t]
        done[t] = chip[k]
    return max(max(bus), max(chip)), np.asarray(done, np.float64)


def expect(cfg: dict, aged: reference.PlainFTL, inp: dict,
           dtype=np.float64) -> dict:
    """Counters, op count, end time, fresh MB/s and every request's
    latency for one query, on a copy of the aged drive ``aged``."""
    import copy
    ftl = copy.deepcopy(aged)
    spp = sectors_per_page(cfg)
    cls, req, gc, rmw = translate(ftl, inp["start_sector"],
                                  inp["n_sectors"], spp)
    ch, ways = cfg["channels"], cfg["ways"][0]
    point = (cfg["interfaces"][0], cfg["cells"][0], ways)
    tab = reference.stacked_tables(cfg, [point])
    req_arr = np.asarray(inp["arrival_us"], np.float64)
    arrival = req_arr[req]
    end, done = simulate_one(tab, cls, ch, ways, arrival, dtype)
    host = ~gc
    lat = np.zeros(len(req_arr))
    np.maximum.at(lat, req[host], done[host])
    lat -= req_arr
    fresh = None
    if gc.any():
        f_end, _ = simulate_one(tab, cls[host], ch, ways, arrival[host],
                                dtype)
        payload = float(tab["bytes"][0, cls[host & ~rmw]].sum())
        fresh = payload / f_end
    counts = dict(ftl.counts)
    counts["rmw_reads"] = int(rmw.sum())
    counts["host_bytes_written"] = int(
        np.sum(np.asarray(inp["n_sectors"], np.int64)[
            np.asarray(inp["op_cls"]) == reference.WRITE])
        * cfg["sector_bytes"])
    return {"counts": counts, "n_ops": len(cls), "end_us": end,
            "fresh_mb_s": fresh, "lat_us": lat}
