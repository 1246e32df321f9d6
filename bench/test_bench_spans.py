"""The reductions of the simulator's own spans (``program_spans``), on
synthetic events whose answers are known, and on the committed chip
trace, which predates the spans: there they read nothing, and the
harness's readers read what they read on the chip.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import program_spans as ps  # noqa: E402
import run  # noqa: E402
import trace_reduce as tr  # noqa: E402

MS = 1_000_000
TESTDATA = BENCH / "testdata"


def aged_query(t0: float) -> list[tuple]:
    """One aged query's spans from ``t0`` (ns, as the trace names and
    times them): 10 ms in all; translation 4 ms with 3 of preconditioning;
    a fold of 140 ops over 256 steps and one of 16 over 16."""
    ev = [("ssd.run", 0, 10, {"call": 1, "ops": 16, "points": 1}),
          ("ssd.translate", 0, 4, {"host_ops": 16, "out_ops": 140,
                                   "machine_runs": 2}),
          ("ssd.precondition", 0.5, 3.5, {"writes": 512, "machine_runs": 1}),
          ("ssd.lower", 4, 4.5, {"ops": 140}),
          ("ssd.stage", 4.5, 4.75, {"steps": 256}),
          ("ssd.fold", 4.75, 7.75, {"engine": "scan", "ops": 140,
                                    "steps": 256, "points": 1}),
          ("ssd.extract", 7.75, 8, {}),
          ("ssd.lower", 8, 8.25, {"ops": 16}),
          ("ssd.stage", 8.25, 8.5, {"steps": 16}),
          ("ssd.fold", 8.5, 9.5, {"engine": "scan", "ops": 16,
                                  "steps": 16, "points": 1}),
          ("ssd.extract", 9.5, 10, {})]
    return [(n, t0 + a * MS, (b - a) * MS, st) for n, a, b, st in ev]


def two_aged_queries():
    host = aged_query(0) + aged_query(20 * MS) + [("bench.query", 0, 1, {})]
    return ps.from_events(host, (0, 30 * MS))


@pytest.mark.parametrize("name,want", [
    ("stage_ms_per_query", 0.5),
    ("fold_wall_ms_per_query", 4.0),
    ("lower_ms_per_query", 0.75),
    ("extract_ms_per_query", 0.75),
    ("precondition_ms_per_query", 3.0),
    ("translate_ms_per_query", 1.0),
    ("fold_pad_pct", 100.0 * (1 - 156 / 272)),
    ("ftl_machine_runs_per_query", 2.0),
])
def test_stage_numbers_on_synthetic_queries(name, want):
    spans = two_aged_queries()
    assert len(spans) == 22, "only ssd. events are kept"
    assert ps.METRICS[name](spans, 2) == pytest.approx(want)
    # nothing to read: no spans (a program without them), or no queries
    assert ps.METRICS[name]([], 2) is None
    if name != "fold_pad_pct":
        assert ps.METRICS[name](spans, 0) is None


def test_spans_clip_to_the_window_and_sort_outer_first():
    host = [("ssd.fold", 5, 10, {"ops": 1}), ("ssd.run", 0, 20, {}),
            ("ssd.stage", -10, 15, {"steps": 8}), ("ssd.lower", 30, 5, {}),
            ("ssd.extract", -10, 5, {}), ("bench.query", 0, 20, {})]
    spans = ps.from_events(host, (0, 25))
    # a span cut by the window keeps its stats and loses the rest
    assert spans == [("run", 0, 20, {}), ("stage", 0, 5, {"steps": 8}),
                     ("fold", 5, 15, {"ops": 1})]
    assert ps.stage_ms(spans, "stage") == pytest.approx(5e-6)
    assert ps.stage_ms(spans, "fold") == pytest.approx(10e-6)


def test_query_coverage_and_fold_device_share():
    spans = two_aged_queries()
    cover = ps.query_coverage(spans, [(0, 10 * MS), (20 * MS, 40 * MS)])
    assert cover == pytest.approx([1.0, 0.5])
    # the device ran 2 ms inside the first fold and all through the
    # second fold of the first query
    folds = ps.fold_device_cover(
        spans, [(5 * MS, 7 * MS), (8.5 * MS, 9.5 * MS)])
    assert [f["device_share"] for f in folds] == pytest.approx(
        [2 / 3, 1.0, 0.0, 0.0])
    assert folds[0]["ms"] == pytest.approx(3.0)
    assert folds[0]["steps"] == 256 and folds[0]["engine"] == "scan"


def test_idle_goes_to_the_innermost_open_span():
    ms = MS
    host = [("bench.window", 0, 30 * ms), ("bench.generate", 0, 2 * ms),
            ("bench.query", 2 * ms, 10 * ms)]
    r = tr.from_events({0: [("jit_fold(1)", 3 * ms, ms),
                            ("jit_trace_completions_masked(2)", 7 * ms,
                             2 * ms)]}, host, (0, 12 * ms))
    plain = dict(tr.innermost_idle(r))
    r.program_spans = ps.from_events(
        [("ssd.run", 2.5 * ms, 9 * ms, {}),
         ("ssd.translate", 2.5 * ms, 3 * ms, {}),
         ("ssd.fold", 6 * ms, 4 * ms, {})], r.window)
    idle = dict(tr.innermost_idle(r))
    assert tr.breakdown(r)["idle_gaps"] == tr.innermost_idle(r)
    # 0-2 generate; 2-2.5 query; 2.5-3 and 4-5.5 translate; 5.5-6 run;
    # 6-7 and 9-10 fold; 10-11.5 run; 11.5-12 query
    assert idle["generate"] == pytest.approx(2e-3)
    assert idle["query"] == pytest.approx(0.5e-3 + 0.5e-3)
    assert idle["ssd.translate"] == pytest.approx(0.5e-3 + 1.5e-3)
    assert idle["ssd.run"] == pytest.approx(0.5e-3 + 1.5e-3)
    assert idle["ssd.fold"] == pytest.approx(2e-3)
    assert "harness" not in idle
    assert sum(idle.values()) == pytest.approx(
        r.window_s - r.busy_s(0))
    # with no program spans the gaps go to the harness's spans
    assert plain == pytest.approx({"generate": 2e-3, "query": 7e-3})


def test_committed_chip_trace_reads_as_before():
    """The trace recorded on one TPU v5e chip holds no program spans: the
    per-stage numbers read nothing, and the harness's readers read
    exactly the values its run printed."""
    path = TESTDATA / "design_sweep_1024ops.xplane.pb"
    want = json.loads((TESTDATA / "small_trace_result.json").read_text())
    dev, host, prog = tr.read_xplane(str(path))
    queries = [(s, s + d) for n, s, d in host
               if n == tr.SPAN_PREFIX + "query"]
    window = (min(a for a, _ in queries), max(b for _, b in queries))
    r = tr.from_events(dev, host, window)
    r.queries = len(queries)
    r.point_ops = want["trace_point_ops"]
    r.compiles = 0
    for name, m in want["metrics"].items():
        assert run.load_metric(name)(r) == m["value"], name
    assert run.load_metric("ftl_translate_ms_per_query")(r) is None
    assert prog == []
    spans = ps.from_events(prog, window)
    assert spans == []
    lines = ps.device_lines(str(path))
    assert all(f(spans, r.queries) is None for f in ps.METRICS.values())
    assert lines["/device:TPU:0"]["XLA Modules"] == len(dev[0]) == 4
    assert lines["/device:TPU:0"]["XLA Ops"] == 6624


def test_reduced_window_holds_the_program_spans():
    """The harness's reduction clips the program's spans to the window
    of whole queries; spans outside it are dropped."""
    host = [("bench.window", 0, 60 * MS), ("bench.query", 0, 10 * MS),
            ("bench.generate", 10 * MS, 10 * MS),
            ("bench.query", 20 * MS, 10 * MS)]
    prog = (aged_query(0) + aged_query(20 * MS)
            + [("ssd.run", 40 * MS, 5 * MS, {"call": 3})])
    r = run.reduce_events({0: [("jit_fold(7)", MS, MS)]}, host, prog)
    assert r.window == (0, 30 * MS) and r.queries == 2
    assert r.program_spans == ps.from_events(prog, r.window)
    assert len(r.program_spans) == 22
    assert r.program_spans[0] == ("run", 0, 10 * MS,
                                  {"call": 1, "ops": 16, "points": 1})
    # the idle time goes to the program's stages
    idle = dict(tr.breakdown(r)["idle_gaps"])
    assert idle["ssd.precondition"] == pytest.approx(2 * 3e-3 - 1e-3)
    assert "query" not in idle


def test_committed_chip_trace_has_no_program_spans():
    r = run.reduce_events(*tr.read_xplane(
        str(TESTDATA / "design_sweep_1024ops.xplane.pb")))
    assert r.queries > 0 and r.program_spans == []
    assert all(run.load_metric(name)(r) is None for name in ps.METRICS)


@pytest.mark.parametrize("name", sorted(ps.METRICS))
def test_span_metric_reads_as_program_spans(name):
    r = tr.Reduced(programs={}, spans=[], window=(0, 30 * MS),
                   program_spans=two_aged_queries(), queries=2)
    got = run.load_metric(name)(r)
    assert got is not None
    assert got == ps.METRICS[name](r.program_spans, r.queries)
