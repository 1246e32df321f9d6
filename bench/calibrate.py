"""Readings that a cell's limits are set from, for many seeds in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 3

For each seed it runs a short window of the cell's own queries at the
cell's own sizes and prints, as one JSON line, the worst reading of each
compared number over the run's check sample: first the program's
(``"side": "program"``), then the control's (``"side": "control"``),
which is the plain reference computed in bfloat16, the precision below
the float32 that the configurations state, put in the program's place.
A limit lies above every program reading and below every control
reading.  The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json

import ml_dtypes
import numpy as np

import run

CONTROL_DTYPE = ml_dtypes.bfloat16


def control_answers(kind, traffic, cfg, seed, n) -> list:
    """The control's answers to the run's check sample."""
    idx = set(run.check_sample(n, seed, traffic["check_queries"]))
    return [kind.expect(run.generate.query_input(traffic, cfg, seed, i),
                        dtype=CONTROL_DTYPE) if i in idx else None
            for i in range(n)]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="run the control on the first this many seeds")
    args = ap.parse_args(argv)
    c, kind, devices = run.setup(args.workload)
    tr, cfg = c["traffic"], c["config"]
    seeds = [int(s) for s in args.seeds.split(",")]
    run.warm_up(kind, c, seeds[0])
    for k, seed in enumerate(seeds):
        win = run.run_queries(kind, tr, cfg, seed, args.seconds)
        n = len(win["answers"])
        out = {"workload": args.workload, "seed": seed, "queries": n,
               "failed": win["failed"]}
        print(json.dumps({**out, "side": "program",
                          **run.worst_readings(kind, tr, cfg, seed,
                                              win["answers"])}),
              flush=True)
        if k >= args.control_seeds:
            continue
        ctl = control_answers(kind, tr, cfg, seed, n)
        with np.errstate(over="ignore", invalid="ignore"):
            print(json.dumps({**out, "side": "control",
                              **run.worst_readings(kind, tr, cfg, seed, ctl)}),
                  flush=True)


if __name__ == "__main__":
    main()
