"""The system under test: how each kind of query calls the simulator.

A traffic file names its ``query`` kind.  Each kind builds its session
at set-up, turns one generated input into the program's own types and
calls ``repro.api.Simulator`` (inside the timed span), counts the work
the user asked for, and says how its answer compares with the plain
reference (``bench/reference.py``).  Nothing here reaches into the
program's internals: tables, sessions and answers go through the public
API, and the reference never sees them.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import generate
import reference


def _rel(a, b) -> float:
    """Widest gap between ``a`` and ``b`` as a share of ``b``,
    elementwise; inf where the shapes differ or ``a`` is missing."""
    return _gap(a, b, b)


def _gap(a, b, scale) -> float:
    """Widest gap between ``a`` and ``b`` as a share of ``scale``; inf
    where the shapes differ or ``a`` is missing."""
    if a is None:
        return float("inf")
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if a.shape != b.shape:
        return float("inf")
    with np.errstate(divide="ignore", invalid="ignore"):
        gap = np.abs(a - b) / np.abs(np.asarray(scale, np.float64))
    return float(np.max(np.where(np.isnan(gap), np.inf, gap)))


def _ssd(cfg: dict, interface: str, cell: str, ways: int):
    from repro.api import CellType, InterfaceKind, SSDConfig
    return SSDConfig(interface=InterfaceKind(interface), cell=CellType(cell),
                     channels=cfg["channels"], ways=ways,
                     policy=cfg["issue_policy"])


class Sweep:
    """``Simulator.sweep(tables, trace)`` on the default engine, once for
    each number of ways in the configuration: the trace placed
    round-robin over that many ways, under the tables of every interface
    and cell at those ways.  Answer: end times [P], in the
    configuration's point order."""

    def __init__(self, cfg: dict, traffic: dict):
        from repro.api import Simulator, op_class_table
        self.cfg = cfg
        self.points = reference.design_points(cfg)
        self.sim = Simulator.for_config(_ssd(cfg, *self.points[-1]))
        self.groups = {w: [i for i, p in enumerate(self.points) if p[2] == w]
                       for w in cfg["ways"]}
        self.tables = {w: [op_class_table(_ssd(cfg, *self.points[i]))
                           for i in idx] for w, idx in self.groups.items()}
        n, ch = traffic["n_ops"], cfg["channels"]
        self.placement = {w: generate.round_robin(n, ch, w)
                          for w in self.groups}

    def call(self, inp: dict) -> dict:
        from repro.api import OpTrace
        end = np.zeros(len(self.points))
        for w, idx in self.groups.items():
            chan, way, par = self.placement[w]
            trace = OpTrace(cls=inp["cls"], channel=chan, way=way,
                            parity=par, channels=self.cfg["channels"],
                            ways=w)
            end[idx] = np.asarray(self.sim.sweep(self.tables[w], trace))
        return {"end_us": end}

    def work(self, inp: dict) -> int:
        return len(self.points) * len(inp["cls"])

    def expect(self, inp: dict, dtype=np.float64) -> dict:
        tab = reference.stacked_tables(self.cfg, self.points)
        ch, n = self.cfg["channels"], len(inp["cls"])
        chan, way, par = (np.stack(a) for a in zip(*(
            reference.stripe(n, ch, p[2]) for p in self.points)))
        return {"end_us": reference.simulate(
            tab, inp["cls"], chan, way, par, ch, max(self.cfg["ways"]),
            dtype=dtype)}

    @staticmethod
    def readings(got: dict, want: dict) -> dict:
        return {"end_rel_gap": _rel(got["end_us"], want["end_us"])}


class Workload:
    """``Simulator.run(stream, sched_policy=...)`` at one design point:
    requests lowered by a static policy, folded with per-op completions.
    Answer: end time and every request's latency."""

    def __init__(self, cfg: dict, traffic: dict):
        from repro.api import Simulator
        self.cfg = cfg
        d = traffic["design"]
        self.point = (d["interface"], d["cell"], d["ways"])
        self.policy = traffic["sched_policy"]
        if self.policy != "stripe":
            raise ValueError("the reference lowers by stripe only")
        self.sim = Simulator.for_config(_ssd(cfg, *self.point))

    def call(self, inp: dict) -> dict:
        from repro.api import RequestStream
        n = len(inp["op_cls"])
        stream = RequestStream(arrival_us=inp["arrival_us"],
                               op_cls=inp["op_cls"],
                               n_pages=np.ones(n, np.int32),
                               stream=np.zeros(n, np.int32))
        res = self.sim.run(stream, sched_policy=self.policy)
        return {"end_us": res.end_us, "lat_us": res.request_lat_us,
                "p50_us": res.p50_us, "p99_us": res.p99_us}

    def work(self, inp: dict) -> int:
        return len(inp["op_cls"])

    def expect(self, inp: dict, dtype=np.float64) -> dict:
        tab = reference.stacked_tables(self.cfg, [self.point])
        n = len(inp["op_cls"])
        chan, way, par = reference.stripe(n, self.cfg["channels"],
                                          self.point[2])
        end, done = reference.simulate(
            tab, inp["op_cls"], chan, way, par, self.cfg["channels"],
            self.point[2], arrival=inp["arrival_us"], dtype=dtype,
            completions=True)
        lat = done[0].astype(np.float64) - inp["arrival_us"]
        return {"end_us": end[0], "lat_us": lat,
                "p50_us": np.percentile(lat, 50),
                "p99_us": np.percentile(lat, 99)}

    @staticmethod
    def readings(got: dict, want: dict) -> dict:
        return {"end_rel_gap": _rel(got["end_us"], want["end_us"]),
                "lat_rel_gap": _gap(got["lat_us"], want["lat_us"],
                                    want["end_us"]),
                "p99_rel_gap": _rel(got["p99_us"], want["p99_us"])}


FTL_COUNTS = ("host_pages_written", "total_pages_written", "gc_pages_moved",
              "gc_reads", "gc_writes", "erases", "free_page_low_watermark")


class AgedRun:
    """``Simulator.run(stream, ftl=spec)`` on the configuration's aged
    drive, default engine and policy: translation with GC, lowering, the
    aged fold with completions and the fresh (GC-free) fold.  Answer:
    the FTL counters, op count, end time, fresh bandwidth and every
    request's latency."""

    def __init__(self, cfg: dict, traffic: dict):
        from repro.api import FTLSpec, Simulator
        self.cfg = cfg
        f = cfg["ftl"]
        self.point = (cfg["interfaces"][0], cfg["cells"][0], cfg["ways"][0])
        self.spec = FTLSpec(
            blocks=f["blocks"], pages_per_block=f["pages_per_block"],
            overprovision=f["overprovision"], gc_policy=f["gc_policy"],
            gc_free_blocks=f["gc_free_blocks"], map_us=f["map_us"],
            precondition=f["precondition"],
            precondition_passes=f["precondition_passes"],
            seed=f["precondition_seed"])
        self.sim = Simulator.for_config(_ssd(cfg, *self.point))
        self._aged = None

    def call(self, inp: dict) -> dict:
        from repro.api import RequestStream
        n = len(inp["op_cls"])
        stream = RequestStream(arrival_us=inp["arrival_us"],
                               op_cls=inp["op_cls"],
                               n_pages=np.ones(n, np.int32),
                               stream=np.zeros(n, np.int32), lpn=inp["lpn"])
        res = self.sim.run(stream, ftl=self.spec)
        stats = dataclasses.asdict(res.ftl_stats)
        return {"counts": {k: int(stats[k]) for k in FTL_COUNTS},
                "n_ops": res.n_ops, "end_us": res.end_us,
                "fresh_mb_s": res.fresh_mb_s, "lat_us": res.request_lat_us}

    def work(self, inp: dict) -> int:
        return len(inp["op_cls"])

    def expect(self, inp: dict, dtype=np.float64) -> dict:
        import copy
        if self._aged is None:
            self._aged = reference.aged_ftl(self.cfg)
        ftl = copy.deepcopy(self._aged)
        cls, src, gc = reference.translate(ftl, inp["op_cls"], inp["lpn"])
        ch, ways = self.cfg["channels"], self.point[2]
        tab = reference.stacked_tables(self.cfg, [self.point])
        arrival = np.asarray(inp["arrival_us"], np.float64)[src]
        end, done = reference.simulate(
            tab, cls, *reference.stripe(len(cls), ch, ways), ch, ways,
            arrival=arrival, dtype=dtype, completions=True)
        host = ~gc
        lat = np.zeros(len(inp["op_cls"]))
        np.maximum.at(lat, src[host], done[0][host].astype(np.float64))
        lat -= inp["arrival_us"]
        f_cls = cls[host]
        f_end = reference.simulate(
            tab, f_cls, *reference.stripe(len(f_cls), ch, ways), ch, ways,
            arrival=arrival[host], dtype=dtype)
        payload = float(tab["bytes"][0, f_cls].sum())
        return {"counts": dict(ftl.counts), "n_ops": len(cls),
                "end_us": end[0], "fresh_mb_s": payload / float(f_end[0]),
                "lat_us": lat}

    @staticmethod
    def readings(got: dict, want: dict) -> dict:
        miss = sum(got["counts"][k] != want["counts"][k] for k in FTL_COUNTS)
        return {"count_mismatch": float(miss
                                        + (got["n_ops"] != want["n_ops"])),
                "end_rel_gap": _rel(got["end_us"], want["end_us"]),
                "fresh_rel_gap": _rel(got["fresh_mb_s"], want["fresh_mb_s"]),
                "lat_rel_gap": _gap(got["lat_us"], want["lat_us"],
                                    want["end_us"])}


KINDS = {"sweep": Sweep, "workload": Workload, "aged_run": AgedRun}
