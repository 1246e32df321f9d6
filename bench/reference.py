"""The plain reference the benchmark checks the simulator against.

It imports nothing of the program.  Every timing comes from the numbers
in the configuration file, and the recurrence is an explicit event loop
over the ops in float64 (or a lower precision, for the control).

The recurrence (per op on channel c, way w, eager issue):

    ready          = max(chip_free[c, w], arrival) + cmd + pre
    start          = max(bus_free[c], ready, ctrl_free) + arb
    bus_free[c]    = start + slot
    ctrl_free      = start + ctrl
    chip_free[c,w] = bus_free[c] + post(page parity)

and the drive's end time is the latest bus or chip release.  The
page-mapped FTL below is the textbook one: an append-only write
frontier, greedy victim selection, relocation of a victim's valid pages
and an erase, triggered while the free pool is at or below its floor.
"""

from __future__ import annotations

import collections

import numpy as np

READ, WRITE = 0, 1
FTL_READ, FTL_WRITE, GC_READ, GC_WRITE, ERASE = 2, 3, 4, 5, 6
FIELDS = ("cmd", "pre", "slot", "post_lo", "post_hi", "ctrl", "arb", "bytes")


# ---------------------------------------------------------------------------
# timing tables from the configuration's numbers
# ---------------------------------------------------------------------------


def design_points(cfg: dict) -> list[tuple[str, str, int]]:
    """(interface, cell, ways) of every point, interface outermost."""
    return [(k, c, w) for k in cfg["interfaces"] for c in cfg["cells"]
            for w in cfg["ways"]]


def op_table(cfg: dict, interface: str, cell: str, ways: int) -> dict:
    """float64 [K] columns of one design point: K = 2 (read, write), or
    7 with the FTL's map-charged, GC and erase classes when the
    configuration has an ``ftl`` block."""
    itf, nand = cfg["interface"][interface], cfg["cell"][cell]
    cyc_us = 1e3 / itf["clock_mhz"] * 1e-3
    cmd = itf["cmd_cycles"] * cyc_us
    io = (nand["page_data_bytes"] + nand["page_spare_bytes"]) * cyc_us \
        / itf["bytes_per_cycle"]
    ecc = nand["ecc_cycles"] * cyc_us + nand["ecc_fixed_us"]
    poll = ways * nand["t_poll_cycles"] * cyc_us + cfg["write_poll_fixed_us"]
    rows = [  # cmd, pre, slot, post_lo, post_hi, ctrl, bytes
        (cmd, nand["t_r_us"], io + ecc, 0.0, 0.0, nand["ecc_fixed_us"],
         nand["page_data_bytes"]),
        (cmd, 0.0, io + ecc + poll, nand["t_prog_lo_us"],
         nand["t_prog_hi_us"], nand["ecc_fixed_us"] + poll,
         nand["page_data_bytes"]),
    ]
    ftl = cfg.get("ftl")
    if ftl is not None:
        m = ftl["map_us"]
        r, w = rows
        rows += [
            (r[0], r[1], r[2], r[3], r[4], r[5] + m, r[6]),
            (w[0], w[1], w[2], w[3], w[4], w[5] + m, w[6]),
            (r[0], r[1], r[2], r[3], r[4], r[5] + m, 0),
            (w[0], w[1], w[2], w[3], w[4], w[5] + m, 0),
            (cmd, 0.0, m, nand["t_bers_us"], nand["t_bers_us"], m, 0),
        ]
    col = np.asarray(rows, np.float64).T
    n_ch = cfg["channels"]
    arb = (0.0 if n_ch <= 1 else cfg["ctrl_arb_switch_frac"]
           + cfg["ctrl_arb_scan_frac"] * (n_ch - 1)) * col[5]
    return dict(zip(FIELDS, (*col[:6], arb, col[6])))


def stacked_tables(cfg: dict, points) -> dict:
    """[P, K] columns over design points."""
    tabs = [op_table(cfg, *p) for p in points]
    return {f: np.stack([t[f] for t in tabs]) for f in FIELDS}


# ---------------------------------------------------------------------------
# the recurrence
# ---------------------------------------------------------------------------


def simulate(tab: dict, cls, channel, way, parity, channels: int,
             ways: int, arrival=None, dtype=np.float64,
             completions: bool = False):
    """End times [P] of one op stream under P tables ([P, K] columns),
    and with ``completions`` the per-op completion times [P, T].  The
    placement (``channel``, ``way``, ``parity``) is [T], shared by every
    point, or [P, T], a point's own (a ``ways``-way drive uses ways
    below ``ways``).  ``dtype`` is the precision of every state and
    sum."""
    cls = np.asarray(cls, np.int64)
    p_n, t_n = tab["cmd"].shape[0], len(cls)
    pts = np.arange(p_n)
    chans, wys, par = (np.broadcast_to(np.asarray(a, np.int64), (p_n, t_n))
                       for a in (channel, way, parity))
    cmdpre = (tab["cmd"][:, cls] + tab["pre"][:, cls]).astype(dtype)
    slot = tab["slot"][:, cls].astype(dtype)
    ctrl = tab["ctrl"][:, cls].astype(dtype)
    arb = tab["arb"][:, cls].astype(dtype)
    post = np.where(par % 2 == 1, tab["post_hi"][:, cls],
                    tab["post_lo"][:, cls]).astype(dtype)
    arr = (np.zeros(t_n, dtype) if arrival is None
           else np.asarray(arrival, np.float64).astype(dtype))
    bus = np.zeros((channels, p_n), dtype)
    chip = np.zeros((channels * ways, p_n), dtype)
    ctrl_free = np.zeros(p_n, dtype)
    done = np.zeros((t_n, p_n), dtype) if completions else None
    cs, ks = chans.T.copy(), (chans * ways + wys).T.copy()
    cmdpre, slot, ctrl, arb, post = (a.T.copy() for a in
                                     (cmdpre, slot, ctrl, arb, post))
    for t in range(t_n):
        c, k = cs[t], ks[t]
        ready = np.maximum(chip[k, pts], arr[t]) + cmdpre[t]
        start = np.maximum(np.maximum(bus[c, pts], ready),
                           ctrl_free) + arb[t]
        b = start + slot[t]
        bus[c, pts] = b
        ctrl_free = start + ctrl[t]
        chip[k, pts] = b + post[t]
        if done is not None:
            done[t] = chip[k, pts]
    end = np.maximum(bus.max(axis=0), chip.max(axis=0))
    return (end, done.T) if completions else end


def stripe(n_ops: int, channels: int, ways: int):
    """(channel, way, parity) of ``n_ops`` ops striped over channels,
    then ways; parity counts each chip's ops (MLC lower/upper page)."""
    t = np.arange(n_ops)
    return (t % channels, (t // channels) % ways,
            (t // (channels * ways)) % 2)


# ---------------------------------------------------------------------------
# page-mapped FTL
# ---------------------------------------------------------------------------


class PlainFTL:
    """Page-mapped FTL with greedy GC (fewest valid pages; ties to the
    block filled earliest, then the lowest id)."""

    def __init__(self, blocks: int, ppb: int, logical: int, gc_free: int):
        self.ppb, self.gc_free, self.blocks = ppb, gc_free, blocks
        self.l2p = np.full(logical, -1, np.int64)
        self.p2l = np.full(blocks * ppb, -1, np.int64)
        self.valid = np.zeros(blocks, np.int64)
        self.full = np.zeros(blocks, bool)
        self.fill_seq = np.full(blocks, -1, np.int64)
        self.fill_seq[0] = 0
        self.seq = 1
        self.free = collections.deque(range(1, blocks))
        self.open, self.next = 0, 0
        self.reset_counts()

    def reset_counts(self):
        self.counts = dict(host_pages_written=0, total_pages_written=0,
                           gc_pages_moved=0, gc_reads=0, gc_writes=0,
                           erases=0,
                           free_page_low_watermark=self.free_pages())

    def free_pages(self) -> int:
        return len(self.free) * self.ppb + self.ppb - self.next

    def _watermark(self):
        c = self.counts
        c["free_page_low_watermark"] = min(c["free_page_low_watermark"],
                                           self.free_pages())

    def _program(self, lpn: int):
        if self.next >= self.ppb:
            self.full[self.open] = True
            if not self.free:
                raise RuntimeError("out of free blocks")
            self.open = self.free.popleft()
            self.fill_seq[self.open] = self.seq
            self.seq += 1
            self.next = 0
        ppn = self.open * self.ppb + self.next
        self.next += 1
        self.counts["total_pages_written"] += 1
        old = self.l2p[lpn]
        if old >= 0:
            self.p2l[old] = -1
            self.valid[old // self.ppb] -= 1
        self.l2p[lpn] = ppn
        self.p2l[ppn] = lpn
        self.valid[ppn // self.ppb] += 1

    def _collect(self, emit):
        if not self.full.any():
            raise RuntimeError("GC with no full block")
        # fewest valid pages, then earliest filled: every full block has
        # its own fill_seq, so the key has no ties
        key = np.where(self.full, self.valid * 2**40 + self.fill_seq,
                       np.iinfo(np.int64).max)
        victim = int(np.argmin(key))
        lo = victim * self.ppb
        lpns = self.p2l[lo:lo + self.ppb]
        for lpn in lpns[lpns >= 0]:
            emit(GC_READ)
            self.counts["gc_reads"] += 1
            emit(GC_WRITE)
            self._program(int(lpn))
            self.counts["gc_writes"] += 1
            self.counts["gc_pages_moved"] += 1
        self.full[victim] = False
        self.fill_seq[victim] = -1
        emit(ERASE)
        self.counts["erases"] += 1
        self.free.append(victim)
        self._watermark()

    def write(self, lpn: int, emit):
        emit(FTL_WRITE)
        self.counts["host_pages_written"] += 1
        self._program(lpn)
        while len(self.free) <= self.gc_free:
            self._collect(emit)
        self._watermark()


def _ignore(cls):
    pass


def aged_ftl(cfg: dict) -> PlainFTL:
    """The configuration's drive after its preconditioning, counters
    reset."""
    f = cfg["ftl"]
    total = f["blocks"] * f["pages_per_block"]
    logical = int(total / (1.0 + f["overprovision"]))
    if f["gc_policy"] != "greedy":
        raise ValueError("the reference FTL collects greedily only")
    ftl = PlainFTL(f["blocks"], f["pages_per_block"], logical,
                   f["gc_free_blocks"])
    if f["precondition"]:
        rng = np.random.default_rng(f["precondition_seed"])
        over = rng.integers(0, logical,
                            int(round(f["precondition_passes"] * logical)))
        for lpn in np.concatenate([np.arange(logical), over]):
            ftl.write(int(lpn), _ignore)
        ftl.reset_counts()
    return ftl


def translate(ftl: PlainFTL, cls, lpn):
    """Host ops through the FTL: (op classes, the host op that issued or
    triggered each op, and whether the op is GC traffic).  GC ops take
    the arrival of the host write that triggered them."""
    out_cls: list[int] = []
    out_src: list[int] = []
    for i, (k, a) in enumerate(zip(np.asarray(cls), np.asarray(lpn))):
        if k == READ:
            out_cls.append(FTL_READ)
            out_src.append(i)
            continue

        def emit(c, i=i):
            out_cls.append(c)
            out_src.append(i)
        ftl.write(int(a), emit)
    out = np.asarray(out_cls, np.int64)
    return out, np.asarray(out_src, np.int64), out >= GC_READ
