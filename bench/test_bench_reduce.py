"""The benchmark's reductions and generators, on the CPU and with no chip.

The reductions are checked on synthetic events whose answers are known,
and on a small trace recorded on one TPU v5e chip (``testdata/``): read
back here, it must give the numbers the harness printed on the chip.
The generators must give the same input for the same (seed, query
index), whatever the seed's size.
"""

from __future__ import annotations

import hashlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import generate  # noqa: E402
import run  # noqa: E402
import trace_reduce as tr  # noqa: E402

TESTDATA = BENCH / "testdata"


def reduced(programs, spans, window, **kw) -> tr.Reduced:
    """A Reduced from (name, start, end) lists, names as the trace has
    them (device programs hashed, spans prefixed)."""
    dev = {d: [(f"{n}(123)", a, b - a) for n, a, b in evs]
           for d, evs in programs.items()}
    host = [(tr.SPAN_PREFIX + n, a, b - a) for n, a, b in spans]
    r = tr.from_events(dev, host, window)
    for k, v in kw.items():
        setattr(r, k, v)
    return r


def metric(name):
    return run.load_metric(name)


def test_union_covered_gaps():
    ivs = [(5, 8), (0, 2), (1, 3), (10, 12), (8, 9)]
    assert tr.union(ivs) == [(0, 3), (5, 9), (10, 12)]
    assert tr.covered(ivs, 0, 12) == 3 + 4 + 2
    assert tr.covered(ivs, 2, 6) == 1 + 1
    assert tr.gaps(ivs, 0, 12) == [(3, 5), (9, 10)]
    assert tr.gaps(ivs, -1, 14) == [(-1, 0), (3, 5), (9, 10), (12, 14)]
    assert tr.gaps([], 0, 4) == [(0, 4)]


def test_program_name_strips_hash_only():
    assert tr.program_name("jit_fold(4564964841494774586)") == "jit_fold"
    assert tr.program_name("jit_f(x)") == "jit_f(x)"


def test_busy_union_and_idle_share():
    # two overlapping programs on one chip: busy is their union, 6 of 10
    r = reduced({0: [("a", 0, 4), ("b", 2, 6)]}, [], (0, 10))
    assert r.busy_s(0) == pytest.approx(6e-9)
    assert metric("device_idle_pct")(r) == pytest.approx(40.0)
    # work outside the window does not count
    r = reduced({0: [("a", -5, 5)]}, [], (0, 10))
    assert metric("device_idle_pct")(r) == pytest.approx(50.0)
    # mean over chips
    r = reduced({0: [("a", 0, 10)], 1: [("a", 0, 5)]}, [], (0, 10))
    assert metric("device_idle_pct")(r) == pytest.approx(25.0)
    assert metric("device_idle_pct")(reduced({}, [], (0, 10))) is None


def test_host_ms_per_query_subtracts_device_time():
    # query 1: 0-10 ms with 4 ms on device; query 2: 20-30 ms with 10 ms
    ms = 1_000_000
    r = reduced({0: [("f", 2 * ms, 6 * ms), ("f", 20 * ms, 30 * ms)]},
                [("query", 0, 10 * ms), ("query", 20 * ms, 30 * ms),
                 ("generate", 10 * ms, 20 * ms)], (0, 30 * ms))
    assert metric("host_ms_per_query")(r) == pytest.approx(3.0)
    # a device busy on two chips at once is counted once
    r = reduced({0: [("f", 0, 5 * ms)], 1: [("f", 0, 5 * ms)]},
                [("query", 0, 10 * ms)], (0, 10 * ms))
    assert metric("host_ms_per_query")(r) == pytest.approx(5.0)
    assert metric("host_ms_per_query")(reduced({}, [], (0, 1))) is None


def test_translation_fold_split():
    ms = 1_000_000
    r = reduced({0: [("jit_fold", 0, 3 * ms),
                     ("jit_trace_completions_masked", 3 * ms, 9 * ms),
                     ("jit_fold", 9 * ms, 10 * ms)]},
                [("query", 0, 10 * ms)], (0, 10 * ms),
                queries=2, point_ops=1000)
    assert metric("ftl_translate_ms_per_query")(r) == pytest.approx(2.0)
    assert metric("fold_ns_per_op")(r) == pytest.approx(6 * ms / 1000)
    # nothing to read: no translation program, or no ops
    r2 = reduced({0: [("jit_sweep", 0, ms)]}, [], (0, ms), queries=1,
                 point_ops=0)
    assert metric("ftl_translate_ms_per_query")(r2) is None
    assert metric("fold_ns_per_op")(r2) is None


def test_breakdown_names_gaps_by_open_span():
    r = reduced({0: [("f", 2, 6), ("g", 12, 19)]},
                [("window", 0, 20), ("query", 1, 7), ("generate", 7, 11),
                 ("query", 11, 20)], (0, 20))
    b = tr.breakdown(r)
    assert [n for n, _ in b["device_ops"]] == ["g", "f"]
    assert [t for _, t in b["device_ops"]] == pytest.approx([7e-9, 4e-9])
    idle = dict(b["idle_gaps"])
    # idle 0-2 (1 of it in a query), 6-12 (1 in a query, 4 in generate,
    # 1 in the next query) and 19-20 (in a query)
    assert idle["harness"] == pytest.approx(1e-9)
    assert idle["query"] == pytest.approx(1e-9 + 2e-9 + 1e-9)
    assert idle["generate"] == pytest.approx(4e-9)
    assert sum(idle.values()) == pytest.approx(20e-9 - 11e-9)


def recorded():
    """The trace of a traced run of a 64-point sweep of 1,024 ops (one
    ``Simulator.sweep`` call a query) on one TPU v5e chip, and the
    result line that run printed (with the number of queries the trace
    holds and their point-ops)."""
    want = json.loads((TESTDATA / "small_trace_result.json").read_text())
    return TESTDATA / "design_sweep_1024ops.xplane.pb", want


def test_recorded_chip_trace_reads_as_on_the_chip():
    path, want = recorded()
    dev, host, _ = tr.read_xplane(str(path))
    assert list(dev) == [0], "one TPU plane"
    assert dev[0], "device programs on the XLA Modules line"
    queries = [(s, s + d) for n, s, d in host
               if n == tr.SPAN_PREFIX + "query"]
    assert len(queries) == want["trace_queries"]
    window = (min(a for a, _ in queries), max(b for _, b in queries))
    r = tr.from_events(dev, host, window)
    r.queries = len(queries)
    r.point_ops = want["trace_point_ops"]
    assert r.window_s == pytest.approx(want["device"]["window_s"], rel=1e-12)
    assert r.busy_s(0) == pytest.approx(want["device"]["busy_s"], rel=1e-12)
    assert 0.0 < r.busy_s(0) <= r.window_s
    for name, m in want["metrics"].items():
        got = metric(name)(r) if name != "compiles_in_window" else None
        if got is not None:
            assert got == pytest.approx(m["value"], rel=1e-9), name
    b = tr.breakdown(r)
    ops = want["breakdown"]["device_ops"]
    assert [n for n, _ in b["device_ops"]] == [n for n, _ in ops]
    assert [t for _, t in b["device_ops"]] == pytest.approx(
        [t for _, t in ops], rel=1e-9)
    idle = sum(t for _, t in b["idle_gaps"])
    assert idle == pytest.approx(r.window_s - r.busy_s(0), rel=1e-9)
    assert idle == pytest.approx(sum(
        t for _, t in want["breakdown"]["idle_gaps"]), rel=1e-9)


@pytest.mark.parametrize("traffic", ["mixed_65k", "poisson_9us",
                                     "overwrite_burst_16k"])
def test_generator_repeats_per_seed_and_index(traffic):
    spec = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    cfg_name = ("aged_ddr_slc_4x16" if spec["generator"] == "overwrite_stream"
                else "paper_ddr_4x16")
    cfg = json.loads((BENCH / "configs" / f"{cfg_name}.json").read_text())
    seed = 2**31 + 12345           # seeds beyond 32 signed bits
    a = generate.query_input(spec, cfg, seed, 3)
    b = generate.query_input(spec, cfg, seed, 3)
    c = generate.query_input(spec, cfg, seed, 4)
    d = generate.query_input(spec, cfg, seed + 1, 3)
    arrays = [k for k, v in a.items() if isinstance(v, np.ndarray)]
    for k in arrays:
        np.testing.assert_array_equal(a[k], b[k])
        assert a[k].shape == c[k].shape == d[k].shape
    random = [k for k in arrays if not np.array_equal(a[k], c[k])]
    assert random, "the next query simulates new input"
    assert all(not np.array_equal(a[k], d[k]) for k in random)
    n = spec.get("n_ops", spec.get("n_requests"))
    assert all(len(a[k]) == n for k in arrays)


#: sha256 over the inputs of three (seed, query index) pairs of each
#: committed mix, drawn by the generators as they were before kinds and
#: generators could be files of their own: the same inputs must follow.
BUILTIN_DIGESTS = {
    "mixed_65k":
        "dd8b9a26b6490f2c883969242d06de9418eec934847f503922b455b35776d036",
    "poisson_9us":
        "eefb53c1acb46d93f682d46cb164a30ed418eb4c4f3bea660b19aab4f3dd298d",
    "overwrite_burst_16k":
        "f3052b156c401a37adacdcf97c7464b428ac6d9adaa2868dc474f1a7f06fc846",
}


@pytest.mark.parametrize("traffic", sorted(BUILTIN_DIGESTS))
def test_builtin_generators_draw_as_before(traffic):
    spec = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    cfg_name = ("aged_ddr_slc_4x16" if spec["generator"] == "overwrite_stream"
                else "paper_ddr_4x16")
    cfg = json.loads((BENCH / "configs" / f"{cfg_name}.json").read_text())
    h = hashlib.sha256()
    for seed, index in [(2**31 + 12345, 3), (7, -1), (4294967311, 0)]:
        inp = generate.query_input(spec, cfg, seed, index)
        for k in sorted(inp):
            a = np.asarray(inp[k])
            h.update(k.encode())
            h.update(str(a.dtype).encode())
            h.update(a.tobytes())
    assert h.hexdigest() == BUILTIN_DIGESTS[traffic]


def test_mixed_trace_places_round_robin_with_mlc_parity():
    inp = generate.mixed_trace(generate.rng_for(1, 0),
                               {"n_ops": 40, "read_fraction": 0.7}, {})
    assert inp["cls"].shape == (40,)
    t = np.arange(40)
    for ways in (1, 3, 4):
        chan, way, par = generate.round_robin(40, 4, ways)
        np.testing.assert_array_equal(chan, t % 4)
        np.testing.assert_array_equal(way, (t // 4) % ways)
        # each chip's k-th visit programs the lower (0) or upper (1) page
        for c in range(4):
            for w in range(ways):
                visits = par[(chan == c) & (way == w)]
                np.testing.assert_array_equal(visits,
                                              np.arange(len(visits)) % 2)


def test_poisson_and_overwrite_streams():
    cfg = json.loads((BENCH / "configs" / "aged_ddr_slc_4x16.json")
                     .read_text())
    rng = generate.rng_for(5, 0)
    p = generate.poisson_stream(rng, {"n_requests": 20000,
                                      "mean_interarrival_us": 9.0,
                                      "read_fraction": 0.7}, cfg)
    gaps = np.diff(p["arrival_us"].astype(np.float64))
    assert p["arrival_us"][0] == 0.0 and (gaps >= 0).all()
    assert gaps.mean() == pytest.approx(9.0, rel=0.03)
    assert (p["op_cls"] == generate.READ).mean() == pytest.approx(0.7,
                                                                 abs=0.02)
    o = generate.overwrite_stream(rng, {"n_requests": 5000}, cfg)
    assert (o["arrival_us"] == 0).all() and (o["op_cls"] == 1).all()
    assert 0 <= o["lpn"].min() and o["lpn"].max() < generate.logical_pages(
        cfg)
