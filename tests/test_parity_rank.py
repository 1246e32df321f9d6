"""MLC page parity without a per-op loop.

``trace._finalize`` ranks each op among the ops on its chip with one
stable sort; ``sched.lower_ops`` and the duplicate-free
``sched.lower_static`` take the closed form ``(t // (C * W)) % 2``.
Every case is pinned bit-equal to the per-op counter loop, which this
file keeps as its oracle.
"""

import dataclasses
import glob

import jax
import numpy as np
import pytest

import repro.api as api
from repro.core import ftl, sched
from repro.core import trace as tr
from repro.core import workload as wl
from repro.core.nand import CellType
from repro.core.sim import SSDConfig
from repro.core.spans import PREFIX


def loop_parity(channel, way, channels, ways) -> np.ndarray:
    """The oracle: walk the ops in order, one counter per chip."""
    parity = np.zeros(len(channel), np.int32)
    counts = np.zeros((channels, ways), np.int64)
    for t in range(len(channel)):
        c, w = channel[t], way[t]
        parity[t] = counts[c, w] % 2
        counts[c, w] += 1
    return parity


def loop_lower_static(stream, channels, ways, policy="stripe"):
    """The oracle lowering: placement as the scheduler documents it,
    parity by the loop."""
    cls, arrival, req_id, payload = wl.request_ops(stream)
    slots = np.cumsum(payload) - 1
    if policy == "stripe":
        chan, way = slots % channels, (slots // channels) % ways
    else:
        chan, way = (slots // ways) % channels, slots % ways
    if not payload.all():
        hof = (np.full(stream.n_requests, -1, np.int64)
               if stream.hedge_of is None
               else np.asarray(stream.hedge_of, np.int64))
        h = hof[req_id]
        is_h = h >= 0
        chan = np.where(~payload & ~is_h, (chan + 1) % channels, chan)
        if is_h.any():
            reps = np.asarray(stream.n_pages, np.int64)
            starts = np.cumsum(reps) - reps
            src = starts[np.clip(h, 0, None)] + (np.arange(len(cls))
                                                 - starts[req_id])
            chan = np.where(is_h, (chan[src] + 1) % channels, chan)
            way = np.where(is_h, (way[src] + 1) % ways, way)
    return tr.OpTrace(
        cls=cls, channel=chan.astype(np.int32), way=way.astype(np.int32),
        parity=loop_parity(chan, way, channels, ways),
        channels=channels, ways=ways,
        payload=None if payload.all() else payload,
        arrival_us=None if not np.any(arrival) else arrival)


def assert_traces_equal(a: tr.OpTrace, b: tr.OpTrace):
    for f in dataclasses.fields(tr.OpTrace):
        x, y = getattr(a, f.name), getattr(b, f.name)
        if isinstance(x, np.ndarray) or isinstance(y, np.ndarray):
            assert x is not None and y is not None, f.name
            assert x.dtype == y.dtype, f.name
            np.testing.assert_array_equal(x, y, err_msg=f.name)
        else:
            assert x == y, f.name


@pytest.mark.parametrize("with_payload", [False, True])
@pytest.mark.parametrize("placement", ["random", "one_chip"])
@pytest.mark.parametrize("n", [0, 1, 65, 4099])
@pytest.mark.parametrize("ways", [1, 16])
@pytest.mark.parametrize("channels", [1, 4])
def test_finalize_rank_equals_loop(channels, ways, n, placement,
                                   with_payload):
    rng = np.random.default_rng(1000 * channels + ways + n)
    if placement == "random":
        chan = rng.integers(0, channels, n)
        way = rng.integers(0, ways, n)
    else:                               # every op on the last chip
        chan = np.full(n, channels - 1)
        way = np.full(n, ways - 1)
    cls = rng.integers(0, 2, n)
    payload = rng.random(n) < 0.7 if with_payload else None
    out = tr._finalize(cls, chan, way, channels, ways, payload=payload)
    want = loop_parity(chan.astype(np.int32), way.astype(np.int32),
                       channels, ways)
    assert out.parity.dtype == np.int32 and out.parity.shape == (n,)
    np.testing.assert_array_equal(out.parity, want)
    np.testing.assert_array_equal(out.channel, chan)
    np.testing.assert_array_equal(out.way, way)
    if with_payload:
        np.testing.assert_array_equal(out.payload, payload)
    else:
        assert out.payload is None


@pytest.mark.parametrize("with_payload", [False, True])
@pytest.mark.parametrize("n", [0, 1, 4099])
@pytest.mark.parametrize("policy", sched.STATIC_POLICIES)
def test_lower_ops_is_the_chunk_at_offset_zero(policy, n, with_payload):
    rng = np.random.default_rng(n)
    cls = rng.integers(0, 7, n).astype(np.int32)
    arrival = np.sort(rng.random(n) * 100).astype(np.float32)
    payload = rng.random(n) < 0.8 if with_payload else None
    got = sched.lower_ops(cls, arrival, 4, 16, policy, payload=payload)
    chunk, nxt = sched.lower_ops_chunk(cls, arrival, 4, 16, policy,
                                       payload=payload, slot_offset=0)
    assert nxt == n
    assert_traces_equal(got, chunk)
    np.testing.assert_array_equal(
        got.parity, loop_parity(got.channel, got.way, 4, 16))


def _hedged_linked():
    return wl.with_hedges(wl.poisson_stream(700, 3.0, read_fraction=0.8,
                                            pages_per_request=3, seed=5),
                          0.3, after_us=2.0, seed=6)


def _hedged_adjacent():
    # the legacy layout: duplicates right after their primary, unlinked
    return dataclasses.replace(
        wl.with_hedges(wl.poisson_stream(700, 3.0, read_fraction=0.8,
                                         seed=7), 0.3, seed=8),
        hedge_of=None)


@pytest.mark.parametrize("policy", sched.STATIC_POLICIES)
@pytest.mark.parametrize("build,ranked", [
    (_hedged_linked, True), (_hedged_adjacent, True),
    (lambda: wl.poisson_stream(700, 3.0, read_fraction=0.5,
                               pages_per_request=2, seed=9), False)],
    ids=["hedge_of", "adjacent", "plain"])
def test_lower_static_equals_loop_lowering(build, ranked, policy):
    stream = build()
    lw = sched.lower_static(stream, 4, 16, policy)
    assert_traces_equal(lw.trace, loop_lower_static(stream, 4, 16, policy))
    assert lw.ranked_ops == (lw.trace.n_ops if ranked else 0)
    assert (lw.trace.payload is not None) == ranked


def _traced_lower_spans(tmp_path, fn):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    from jax.profiler import ProfileData
    return [dict(e.stats)
            for plane in ProfileData.from_file(path[-1]).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name == PREFIX + "lower"]


@pytest.mark.parametrize("kind", ["plain", "hedged", "ftl"])
def test_lower_span_counts_ranked_ops(tmp_path, kind):
    sim = api.Simulator(SSDConfig(cell=CellType.MLC, channels=2, ways=4))
    stream = wl.poisson_stream(200, 5.0, read_fraction=0.9, seed=3)
    kw = {}
    if kind == "hedged":
        stream = wl.with_hedges(stream, 0.5, seed=4)
    elif kind == "ftl":
        stream = wl.overwrite_stream(300, 150, seed=4)
        kw["ftl"] = ftl.FTLSpec(blocks=32, pages_per_block=8,
                                overprovision=0.25, precondition=True)
    sim.run(stream, **kw)                           # compile outside
    spans = _traced_lower_spans(tmp_path, lambda: sim.run(stream, **kw))
    if kind == "ftl":                               # aged and fresh folds
        assert [s["ranked_ops"] for s in spans] == [0, 0]
        return
    (stats,) = spans
    assert stats["ops"] == stream.n_requests
    if kind == "hedged":
        assert stats["ranked_ops"] == int(np.sum(stream.n_pages)) > 200
    else:
        assert stats["ranked_ops"] == 0
