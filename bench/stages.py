"""A cell's query time put down to the simulator's stages, on the chip.

    python3 bench/stages.py --workload <cell> --seed <n> [--seconds 3]
                            [--untraced <s>]

Builds the cell's session and warms it up as ``bench/run.py`` does.
With ``--untraced`` it first runs a closed-loop window of that many
seconds with the profiler off.  It then runs ``--seconds`` of whole
queries with the profiler on, reads the trace and prints one JSON
object:

* ``stages``: each number of ``program_spans.METRICS``;
* ``harness``: the device-trace metrics of ``HARNESS_METRICS`` as the
  harness reads them from the same trace;
* ``idle``: the window's device-idle time put down to the innermost
  open span, program or harness;
* ``query_coverage``: the share of each ``bench.query`` span that
  program spans cover (least and mean);
* ``folds``: each ``ssd.fold`` span's stats, length and the share of it
  in which a device ran a program;
* ``device_lines``: events per line of each device plane, where a
  dropped program shows as a missing event;
* ``traced_ops_per_s`` and ``untraced_ops_per_s``: the work asked for
  over each window's wall time;
* ``trace_s``: seconds to write the trace and to read it back, and
  ``trace_bytes``: its size.

The trace is deleted after it is read.  Nothing is checked against the
reference here: ``bench/run.py`` does that.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import program_spans  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402

#: The per-layer metrics the harness reads from a trace.
HARNESS_METRICS = ("device_idle_pct", "fold_ns_per_op",
                   "ftl_translate_ms_per_query", "host_ms_per_query")


def traced_window(kind, c: dict, seed: int, seconds: float):
    """(window result, xplane path, its temporary directory, seconds to
    write it) of ``seconds`` of whole queries with the profiler on."""
    import jax
    out = tempfile.mkdtemp(prefix="bench_stages_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        win = run.run_queries(kind, c["traffic"], c["config"], seed,
                              seconds)
    finally:
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        write_s = time.perf_counter() - t0
    path = sorted(Path(out).rglob("*.xplane.pb"))[-1]
    return win, path, out, write_s


def reduce(path, win) -> dict:
    """Everything this tool reports from one trace file."""
    r = run.reduce_events(*trace_reduce.read_xplane(str(path)))
    r.point_ops = sum(win["work"][:r.queries])
    spans = r.program_spans
    cover = program_spans.query_coverage(spans, r.query_spans())
    return {
        "queries": r.queries,
        "window_s": r.window_s,
        "stages": {name: f(spans, r.queries)
                   for name, f in program_spans.METRICS.items()},
        "harness": {name: run.load_metric(name)(r)
                    for name in HARNESS_METRICS},
        "idle": trace_reduce.innermost_idle(r, top=12),
        "query_coverage": {"least": min(cover),
                           "mean": sum(cover) / len(cover)},
        "folds": program_spans.fold_device_cover(
            spans, r.all_devices_intervals())[:8],
        "device_lines": program_spans.device_lines(str(path)),
        "device_ops": trace_reduce.breakdown(r)["device_ops"],
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=run.TRACE_SECONDS)
    ap.add_argument("--untraced", type=float, default=0.0)
    args = ap.parse_args(argv)
    c, kind, _ = run.setup(args.workload)
    run.warm_up(kind, c, args.seed)
    res = {"workload": args.workload, "seed": args.seed}
    if args.untraced > 0:
        # the traced window below draws the same query indices, so the
        # two windows simulate the same inputs
        cold = run.run_queries(kind, c["traffic"], c["config"], args.seed,
                               args.untraced)
        res["untraced_ops_per_s"] = sum(cold["work"]) / cold["wall"]
        res["untraced_queries"] = len(cold["times"])
    win, path, tmp, write_s = traced_window(kind, c, args.seed,
                                            args.seconds)
    try:
        res["traced_ops_per_s"] = sum(win["work"]) / win["wall"]
        res["trace_bytes"] = path.stat().st_size
        print(f"stages: trace of {res['trace_bytes']} B written in "
              f"{write_s:.1f} s", file=sys.stderr, flush=True)
        t0 = time.perf_counter()
        res.update(reduce(path, win))
        res["trace_s"] = {"write": write_s,
                          "read": time.perf_counter() - t0}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
