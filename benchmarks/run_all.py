"""Run every benchmark module and emit a machine-readable BENCH_<n>.json.

    PYTHONPATH=src python benchmarks/run_all.py [--smoke] [--out DIR]

Each run writes ``benchmarks/results/BENCH_<n>.json`` (n = one past the
highest existing index) holding every benchmark row (name/value/paper),
per-section wall time, and the environment — so the perf trajectory of
the engines is tracked across PRs by diffing the JSON files.

``--smoke`` shrinks trace lengths for CI: it still executes every
engine and **fails on engine disagreement** — on end times (the
``assert agree < 1e-3`` paths inside ``sweep_bench``), on the
phase-resolved Table 5 / mixed-trace energy totals (the matching
asserts in ``tables.run_table5`` and ``sweep_bench.run_mixed``), and
on the fleet-scale paths (``scale_bench``: streaming vs oracle,
megakernel vs scan, sharded sweep == vmap when several devices are
present), and on the FTL stage
(``ftl_bench``: greedy WAF vs the analytic fixed point, the aging
bandwidth cliff, GC-translated engine agreement) — and, in a full
(non-smoke) run only, on a log-depth speedup < 1, a megakernel
speedup < 2x, or a non-constant-memory streaming fold.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import platform
import re
import sys
import time
import warnings

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

RESULTS = pathlib.Path(__file__).resolve().parent / "results"


def _next_index(out_dir: pathlib.Path) -> int:
    taken = [int(m.group(1))
             for f in out_dir.glob("BENCH_*.json")
             if (m := re.fullmatch(r"BENCH_(\d+)\.json", f.name))]
    return max(taken, default=0) + 1


def _section(name, fn):
    t0 = time.perf_counter()
    rows = fn()
    dt = round(time.perf_counter() - t0, 3)
    print(f"# {name}: {len(rows)} rows in {dt:.1f}s")
    for r in rows:
        print(f"{r['name']},{r['value']},{r['paper']}")
    return {"name": name, "rows": rows, "wall_s": dt}


def _check_speedups(sections, smoke: bool) -> None:
    """The acceptance gates: log-depth engines must beat the O(T) scan
    per design point at the full T (speedup rows > 1; smoke runs at
    reduced T only warn — short traces are overhead-dominated), and the
    ``Simulator`` session cache must serve a repeated identical query
    >= 5x faster than the cold first query (gated even under --smoke:
    cold-vs-warm is compile-dominated, so the ratio is size-robust)."""
    bad, bad_smoke = [], []
    for sec in sections:
        for r in sec["rows"]:
            if r["name"].endswith("_speedup_vs_scan") and r["paper"] == ">1":
                if float(r["value"]) <= 1.0:
                    bad_smoke.append(f"{r['name']} = {r['value']} (want > 1)")
            if r["paper"] == ">=5" and float(r["value"]) < 5.0:
                bad.append(f"{r['name']} = {r['value']} (want >= 5)")
    if bad_smoke:
        msg = "speedup gate rows failed: " + "; ".join(bad_smoke)
        if smoke:
            print(f"# WARNING (smoke sizes, not gating): {msg}")
        else:
            bad += bad_smoke
    if bad:
        raise AssertionError("speedup gate rows failed: " + "; ".join(bad))


def main() -> None:
    # repro-internal code may never reach its own deprecated query shims
    # (DESIGN.md §2.5); the module field keys on the *calling* module.
    # Programmatic because `python -W` re.escapes the module field into
    # an exact match (pytest gets the same rule from pytest.ini).
    warnings.filterwarnings("error", category=DeprecationWarning,
                            module=r"repro\.")
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes for CI; still checks engine "
                         "agreement")
    ap.add_argument("--out", type=pathlib.Path, default=None,
                    help="output dir for BENCH_<n>.json (default: the "
                         "tracked results dir; smoke runs default to a "
                         "temp dir so reduced-size datapoints never "
                         "pollute the cross-PR trajectory)")
    ap.add_argument("--index", type=int, default=None,
                    help="force the BENCH_<n>.json index (default: one "
                         "past the highest existing; use to align the "
                         "committed file with the PR number)")
    args = ap.parse_args()
    if args.out is None:
        if args.smoke:
            import tempfile
            args.out = pathlib.Path(tempfile.mkdtemp(prefix="bench_smoke_"))
        else:
            args.out = RESULTS

    import jax

    from benchmarks import (api_bench, freq, ftl_bench, reliability_bench,
                            scale_bench, sched_bench, sweep_bench, tables)
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    t0 = time.perf_counter()
    sections = [
        _section("freq", freq.run),
        # Simulator session serving path: repeated-query cache speedup,
        # run_many bucket packing, all-five-engine agreement through the
        # unified surface (runs first so its compile shapes are cold)
        _section("api", lambda: api_bench.run(small=args.smoke)),
        _section("table3", tables.run_table3),
        _section("table4", tables.run_table4),
        # trace-level phase-resolved energy; asserts < 1e-3 cross-engine
        # agreement on every cell (the energy half of the smoke gate)
        _section("table5", lambda: tables.run_table5(small=args.smoke)),
        _section("table5_closed_form", tables.run_table5_closed_form),
        _section("sweep", lambda: sweep_bench.run(small=args.smoke)),
        # latency under load: p99-vs-offered-load curves per way count;
        # gates (smoke too): arrival-aware cross-engine agreement and
        # dynamic-dispatch-vs-static-stripe end-time/p99 sanity
        _section("sched", lambda: sched_bench.run(small=args.smoke)),
        # fleet-scale paths (DESIGN.md §2.7); gates: streaming/megakernel
        # cross-engine agreement < 1e-3 + sharded==vmap (smoke too);
        # megakernel >= 2x over per-trace launches and million-op
        # constant-memory streaming in full runs only
        _section("scale", lambda: scale_bench.run(small=args.smoke)),
        # reliability + tail latency (DESIGN.md §2.8); gates (smoke too):
        # faulty-trace cross-engine agreement < 1e-3, hedged p99 <=
        # unhedged under the frozen retry-storm seed, p99 monotone in wear
        _section("reliability",
                 lambda: reliability_bench.run(small=args.smoke)),
        # FTL aging + garbage collection (DESIGN.md §2.10/§2.11); gates
        # (smoke too): greedy WAF within 10% of the analytic fixed point
        # at every overprovisioning ratio, aged < fresh bandwidth
        # whenever GC ran, GC-translated cross-engine agreement < 1e-3,
        # scan translation op-for-op identical to the host oracle; full
        # runs additionally gate the >= 5x fused aged-sweep speedup
        _section("ftl", lambda: ftl_bench.run(small=args.smoke)),
    ]
    _check_speedups(sections, args.smoke)

    args.out.mkdir(parents=True, exist_ok=True)
    n = args.index if args.index is not None else _next_index(args.out)
    payload = {
        "bench_index": n,
        "smoke": args.smoke,
        "wall_s_total": round(time.perf_counter() - t0, 3),
        "env": {"backend": jax.default_backend(),
                "jax": jax.__version__,
                "python": platform.python_version(),
                "machine": platform.machine()},
        "sections": sections,
    }
    path = args.out / f"BENCH_{n}.json"
    path.write_text(json.dumps(payload, indent=1) + "\n")
    print(f"# wrote {path} ({payload['wall_s_total']}s total)")


if __name__ == "__main__":
    main()
