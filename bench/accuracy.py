"""The simulator's error against the paper's measured Tables 3-5.

    PYTHONPATH=src python3 bench/accuracy.py

Every number it prints is a simulated statistic: what the modelled
drive would do, not how fast the simulator runs, so any backend gives
the same figures.  Bandwidths are the program's steady-stream MB/s
(``repro.api.steady_bandwidth_mb_s``, capped by the SATA2 link as the
paper's drive was), energy its controller nJ per byte on the scan
engine.  The paper's values are the copy in ``bench/paper/``.  Only the
CONV and PROPOSED (DDR) columns are compared: those are the benchmark's
design space; SYNC_ONLY is not modelled by its configurations.

Prints one JSON line per table with the mean and largest |relative
error|, then the DDR-over-CONV speed-ups, simulated beside measured.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

PAPER = Path(__file__).resolve().parent / "paper" / "tables_3_5.json"
KINDS = ("conv", "proposed")


def _simulated_mb_s(cell: str, mode: str, channels: int, ways: int,
                    kind: str) -> float:
    from repro.api import CellType, InterfaceKind, SSDConfig
    from repro.api import steady_bandwidth_mb_s
    return steady_bandwidth_mb_s(
        SSDConfig(interface=InterfaceKind(kind), cell=CellType(cell),
                  channels=channels, ways=ways), mode)


def _simulated_nj_per_byte(mode: str, ways: int, kind: str) -> float:
    from repro.api import CellType, InterfaceKind, Simulator, SSDConfig
    from repro.core.trace import READ, WRITE, steady_trace
    sim = Simulator.for_config(SSDConfig(interface=InterfaceKind(kind),
                                         cell=CellType.SLC, channels=1,
                                         ways=ways))
    trace = steady_trace(512, 1, ways, READ if mode == "read" else WRITE)
    return sim.run(trace, objective="energy", engine="scan"
                   ).energy.nj_per_byte


def rows(paper: dict) -> list[dict]:
    cols = paper["columns"]
    out = []
    for r in paper["table3_mb_s"]["rows"]:
        for k in KINDS:
            out.append({"table": 3, **r, "channels": 1, "kind": k,
                        "paper": r["values"][cols.index(k)],
                        "sim": _simulated_mb_s(r["cell"], r["mode"], 1,
                                               r["ways"], k)})
    for r in paper["table4_mb_s"]["rows"]:
        for k in KINDS:
            v = r["values"][cols.index(k)]
            out.append({"table": 4, **r, "kind": k,
                        "paper": 300.0 if v is None else v,
                        "sim": _simulated_mb_s(r["cell"], r["mode"],
                                               r["channels"], r["ways"], k)})
    for r in paper["table5_nj_per_byte"]["rows"]:
        for k in KINDS:
            out.append({"table": 5, **r, "channels": 1, "kind": k,
                        "paper": r["values"][cols.index(k)],
                        "sim": _simulated_nj_per_byte(r["mode"], r["ways"],
                                                      k)})
    for r in out:
        del r["values"]
        r["rel_err"] = (r["sim"] - r["paper"]) / r["paper"]
    return out


def speedups(rs: list[dict]) -> list[dict]:
    """PROPOSED over CONV bandwidth for each Table 3 and 4 design."""
    key = ("table", "cell", "mode", "channels", "ways")
    by = {}
    for r in rs:
        if r["table"] in (3, 4):
            by.setdefault(tuple(r[k] for k in key), {})[r["kind"]] = r
    out = []
    for k, pair in by.items():
        sim = pair["proposed"]["sim"] / pair["conv"]["sim"]
        paper = pair["proposed"]["paper"] / pair["conv"]["paper"]
        out.append({**dict(zip(key, k)), "sim": sim, "paper": paper,
                    "rel_err": (sim - paper) / paper})
    return out


def main() -> None:
    rs = rows(json.loads(PAPER.read_text()))
    for t in (3, 4, 5):
        e = np.abs([r["rel_err"] for r in rs if r["table"] == t])
        print(json.dumps({"table": t, "points": len(e),
                          "mean_abs_rel_err": float(e.mean()),
                          "max_abs_rel_err": float(e.max())}))
    s = speedups(rs)
    e = np.abs([r["rel_err"] for r in s])
    print(json.dumps({"ddr_over_conv_speedups": len(s),
                      "mean_abs_rel_err": float(e.mean()),
                      "max_abs_rel_err": float(e.max())}))
    for r in s:
        print(json.dumps(r))


if __name__ == "__main__":
    main()
