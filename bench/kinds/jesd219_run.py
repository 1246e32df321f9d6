"""``Simulator.run(stream, ftl=spec)`` on JESD219A's enterprise
workload: sector-addressed writes on the configuration's aged drive,
with read-modify-write of the pages a write covers in part.  Answer:
the FTL counters (with ``rmw_reads`` and ``host_bytes_written``), op
count, end time, fresh bandwidth and every request's latency, as
``system.AgedRun`` answers them; the plain reference is
``jesd219_ref``."""

from __future__ import annotations

import dataclasses

import numpy as np

import jesd219_ref
import reference
import system

COUNTS = system.FTL_COUNTS + ("rmw_reads", "host_bytes_written")


def _pages(inp: dict, spp: int) -> np.ndarray:
    """Pages each request covers."""
    first = np.asarray(inp["start_sector"], np.int64) % spp
    return -(-(first + np.asarray(inp["n_sectors"], np.int64)) // spp)


class Kind(system.AgedRun):
    def __init__(self, cfg: dict, traffic: dict):
        super().__init__(cfg, traffic)
        self.spp = jesd219_ref.sectors_per_page(cfg)
        self.spec = dataclasses.replace(self.spec,
                                        sectors_per_page=self.spp)

    def stream(self, inp: dict):
        """The query's requests as the program's sector-addressed
        stream."""
        from repro.api import RequestStream
        start = np.asarray(inp["start_sector"], np.int64)
        n = len(start)
        return RequestStream(
            arrival_us=inp["arrival_us"], op_cls=inp["op_cls"],
            n_pages=_pages(inp, self.spp).astype(np.int32),
            stream=np.zeros(n, np.int32), lpn=start // self.spp,
            first_sector=(start % self.spp).astype(np.int32),
            n_sectors=inp["n_sectors"])

    def call(self, inp: dict) -> dict:
        res = self.sim.run(self.stream(inp), ftl=self.spec)
        stats = dataclasses.asdict(res.ftl_stats)
        return {"counts": {k: int(stats[k]) for k in COUNTS},
                "n_ops": res.n_ops, "end_us": res.end_us,
                "fresh_mb_s": res.fresh_mb_s, "lat_us": res.request_lat_us}

    def work(self, inp: dict) -> int:
        return int(_pages(inp, self.spp).sum())

    def expect(self, inp: dict, dtype=np.float64) -> dict:
        if self._aged is None:
            self._aged = reference.aged_ftl(self.cfg)
        return jesd219_ref.expect(self.cfg, self._aged, inp, dtype)

    @staticmethod
    def readings(got: dict, want: dict) -> dict:
        miss = sum(got["counts"][k] != want["counts"][k] for k in COUNTS)
        fresh = (0.0 if got["fresh_mb_s"] is None
                 and want["fresh_mb_s"] is None
                 else system._rel(got["fresh_mb_s"], want["fresh_mb_s"]))
        return {"count_mismatch": float(miss
                                        + (got["n_ops"] != want["n_ops"])),
                "end_rel_gap": system._rel(got["end_us"], want["end_us"]),
                "fresh_rel_gap": fresh,
                "lat_rel_gap": system._gap(got["lat_us"], want["lat_us"],
                                           want["end_us"])}
