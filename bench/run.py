"""The chip benchmark of the DDR-NAND SSD simulator.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the root of the checkout names each cell's
configuration (``bench/configs/<config>.json``, via the entry's
``file``) and traffic mix (``bench/traffic/<traffic>.json``); each cell
has its limits in ``bench/limits/<cell>.json`` and each per-layer metric
its reader in ``bench/metrics/<metric>.py``.  The harness finds every
piece by its name (``bench/loader.py``).

A cell adds files and entries, and edits no file that is there.  One
with a new query shape adds:

* a ``workloads`` entry, and a ``configs`` entry with its
  ``bench/configs/<config>.json`` where the drive is new;
* ``bench/traffic/<traffic>.json``, naming the mix's ``generator`` and
  ``query`` kind and their parameters;
* ``bench/generators/<generator>.py``, a function ``generate(rng,
  params, cfg)``, where no built-in generator (``generate.GENERATORS``)
  draws the mix;
* ``bench/kinds/<query>.py``, a class ``Kind`` with ``__init__(cfg,
  traffic)``, ``call(inp)``, ``work(inp)``, ``expect(inp,
  dtype=np.float64)`` and ``readings(got, want)`` as the built-in kinds
  (``system.KINDS``) have them, where none of those calls the query;
  its plain reference may be a module of its own beside it;
* ``bench/limits/<cell>.json``, a limit for each of ``readings``;
* for each new per-layer metric, ``bench/metrics/<metric>.py`` with
  ``read(r)`` (``r`` a ``trace_reduce.Reduced``: device programs,
  harness spans and the program's ``ssd.*`` spans with their counters)
  and a ``per_layer`` entry; the cell joins the ``workloads`` list of
  each existing metric it reports.

A run, in one process:

1. set-up: refuse to run without the chips the cell asks for, turn the
   persistent compile cache on, build the cell's session and warm up
   every shape its queries use;
2. the window: a closed loop, like a user's design script -- draw query
   ``i``'s input from ``(seed, i)``, call the simulator, wait for the
   answer, go on -- until ``--seconds`` have passed; whole queries only;
3. with ``--trace 1`` the profiler records the window's first
   ``TRACE_SECONDS`` (whole queries) and the per-layer metrics are read
   from it;
4. the check: a sample of the window's queries, drawn from the seed, is
   computed again by the kind's plain reference (``bench/reference.py``
   for the built-in kinds) and each compared number must stay within
   its limit.

The last line of standard output is the result, as one JSON object.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import generate  # noqa: E402
import loader  # noqa: E402
import program_spans  # noqa: E402
import system  # noqa: E402
import trace_reduce  # noqa: E402

#: Seconds of the window the profiler records in a traced run (whole
#: queries, at least one).  Scan folds emit one device event per loop
#: iteration, so a longer trace costs minutes to write and read.
TRACE_SECONDS = 3.0

#: The event JAX records for each program it compiles or loads from the
#: persistent cache.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_cell(name: str) -> dict:
    """The cell's entry, configuration, traffic, limits and metrics."""
    bench = loader.BENCH
    spec = json.loads((bench.parent / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r} (one of "
                         f"{', '.join(cells)})")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    return {
        "cell": cell,
        "config": json.loads((bench.parent / conf["file"]).read_text()),
        "traffic": json.loads(
            (bench / "traffic" / f"{cell['traffic']}.json").read_text()),
        "limits": json.loads(
            (bench / "limits" / f"{name}.json").read_text()),
        "end_to_end": [m for m in spec["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in spec["per_layer"]
                      if name in m.get("workloads", [name])],
    }


def tpu_devices(chips: int):
    """The TPU devices, or exit non-zero with no result."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU (JAX's default device is "
                         f"{devices[0].platform}); nothing runs on the "
                         "CPU instead")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices


class CompileCounter:
    """Counts compilations (and persistent-cache loads) while on."""

    def __init__(self):
        import jax
        self.on, self.count, self.names = False, 0, []
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if self.on and event == COMPILE_EVENT:
            self.count += 1
            self.names.append(str(kw.get("fun_name", "?")))


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + name)


def run_queries(kind, traffic, cfg, seed: int, seconds: float,
                on_query=None) -> dict:
    """The closed-loop window: whole queries until ``seconds`` passed.
    ``on_query(i, t_elapsed)`` runs after each query (the tracer's
    stop)."""
    times, work, answers, failed = [], [], [], 0
    t0 = q1 = time.perf_counter()
    with _span("window"):
        while True:
            i = len(times)
            with _span("generate"):
                inp = generate.query_input(traffic, cfg, seed, i)
            q0 = time.perf_counter()
            try:
                with _span("query"):
                    ans = kind.call(inp)
            except Exception:            # a failed query is counted
                if not failed:
                    traceback.print_exc()
                failed += 1
                ans = None
            q1 = time.perf_counter()
            times.append(q1 - q0)
            work.append(kind.work(inp))
            answers.append(ans)
            if on_query is not None:
                on_query(i, q1 - t0)
            if q1 - t0 >= seconds:
                break
    return {"times": times, "work": work, "answers": answers,
            "failed": failed, "wall": q1 - t0}


def check_sample(n: int, seed: int, k: int) -> list[int]:
    """``k`` query indices of ``n``, drawn from the seed."""
    rng = np.random.default_rng([int(seed) % 2**64, 2**32 + 7])
    return sorted(rng.choice(n, size=min(k, n), replace=False).tolist())


def worst_readings(kind, traffic, cfg, seed: int, answers) -> dict:
    """Each compared number, the worst over the run's check sample (inf
    where a sampled query gave no answer)."""
    idx = check_sample(len(answers), seed, traffic["check_queries"])
    worst: dict[str, float] = {}
    for i in idx:
        if answers[i] is None:
            worst["no_answer"] = float("inf")
            continue
        want = kind.expect(generate.query_input(traffic, cfg, seed, i))
        for name, v in kind.readings(answers[i], want).items():
            v = float(v) if np.isfinite(v) else float("inf")
            worst[name] = max(worst.get(name, 0.0), v)
    return worst


def check(kind, traffic, cfg, seed: int, answers, limits: dict
          ) -> tuple[bool, dict]:
    """(correct, {name: {value, limit}}) over the run's check sample."""
    worst = worst_readings(kind, traffic, cfg, seed, answers)
    checks = {name: {"value": worst.get(name, float("inf")),
                     "limit": limit} for name, limit in limits.items()}
    ok = "no_answer" not in worst and all(
        ch["value"] <= ch["limit"] for ch in checks.values())
    return ok, checks


def load_metric(name: str):
    """The reader of a per-layer metric."""
    return loader.load("metrics", name).read


def kind_class(query: str):
    """The class of a query kind: built in, else a file of its own."""
    if query in system.KINDS:
        return system.KINDS[query]
    return loader.load("kinds", query).Kind


def reduce_events(device_events, host_events, program_events
                  ) -> trace_reduce.Reduced:
    """A traced window's events (as ``trace_reduce.read_xplane`` gives
    them), reduced; the window runs from the first query's start to the
    last query's end."""
    queries = [(s, s + d) for n, s, d in host_events
               if n == trace_reduce.SPAN_PREFIX + "query"]
    if not queries:
        raise RuntimeError("the trace holds no query span")
    window = (min(a for a, _ in queries), max(b for _, b in queries))
    r = trace_reduce.from_events(device_events, host_events, window)
    r.program_spans = program_spans.from_events(program_events, window)
    r.queries = len(queries)
    return r


class Tracer:
    """Records the window's first ``TRACE_SECONDS`` into a temporary
    directory, then reads and deletes it."""

    def __init__(self):
        import jax
        self.jax = jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.on = True

    def after_query(self, i: int, elapsed: float):
        if elapsed >= TRACE_SECONDS:
            self.stop()

    def stop(self):
        if self.on:
            self.jax.profiler.stop_trace()
            self.on = False

    def reduce(self) -> trace_reduce.Reduced:
        self.stop()
        try:
            files = sorted(Path(self.dir).rglob("*.xplane.pb"))
            if not files:
                raise RuntimeError("the profiler wrote no trace")
            events = trace_reduce.read_xplane(str(files[-1]))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return reduce_events(*events)


def device_info(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": int(max(peaks))}


def setup(name: str, chips_of=tpu_devices):
    """Everything before the warm-up: (cell, kind, devices)."""
    c = load_cell(name)
    devices = chips_of(c["cell"]["chips"])
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch import compile_cache
    compile_cache.use_compile_cache()
    kind = kind_class(c["traffic"]["query"])(c["config"], c["traffic"])
    return c, kind, devices


def warm_up(kind, c: dict, seed: int):
    for j in range(c["traffic"]["warmup_queries"]):
        kind.call(generate.query_input(c["traffic"], c["config"], seed,
                                       -1 - j))


def run(name: str, seed: int, seconds: float, trace: bool,
        chips_of=tpu_devices) -> dict:
    c, kind, devices = setup(name, chips_of)
    warm_up(kind, c, seed)
    setup_s = time.perf_counter() - T_START
    counter = CompileCounter()
    tracer = Tracer() if trace else None
    counter.on = True
    win = run_queries(kind, c["traffic"], c["config"], seed, seconds,
                      tracer.after_query if tracer else None)
    counter.on = False
    device = device_info(devices)
    metrics: dict[str, dict] = {}
    out = {}
    if tracer is None:
        values = {"sim_ops_per_s": sum(win["work"]) / win["wall"],
                  "query_p95_s": float(np.percentile(win["times"], 95)),
                  "setup_s": setup_s}
        for m in c["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        r = tracer.reduce()
        r.point_ops = sum(win["work"][:r.queries])
        r.compiles = counter.count
        n_dev = max(len(r.programs), 1)
        device["busy_s"] = sum(r.busy_s(d) for d in r.programs) / n_dev
        device["window_s"] = r.window_s
        for m in c["per_layer"]:
            v = load_metric(m["name"])(r)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = trace_reduce.breakdown(r)
    if counter.count:
        print(json.dumps({"compiled_in_window": counter.names}),
              file=sys.stderr)
    ok, checks = check(kind, c["traffic"], c["config"], seed,
                       win["answers"], c["limits"])
    return {"correct": bool(ok and not win["failed"]),
            "attempted": len(win["times"]), "failed": win["failed"],
            "metrics": metrics, "device": device, **out, "checks": checks}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    res = run(args.workload, args.seed, args.seconds, bool(args.trace))
    for name, ch in res["checks"].items():
        print(f"check {name}: {ch['value']!r} (limit {ch['limit']!r})",
              file=sys.stderr)
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    with contextlib.suppress(BrokenPipeError):
        main()
