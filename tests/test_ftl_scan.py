"""The compiled FTL translation engine (DESIGN.md §2.11): the lax.scan
machine must be op-for-op the host translator — same op classes,
arrivals, payloads, request ids, GC flags, stats, erase counts and
final drive state — across the policy × geometry × overprovisioning
grid, errors included; the fused sweep and the chunked streaming
variant must reproduce the per-point / one-shot answers exactly; and
the FTL sub-session cache must stay LRU-bounded."""

import dataclasses

import numpy as np
import pytest

import repro.api as api
from repro.core import ftl, ftl_scan, sched
from repro.core.nand import CellType
from repro.core.sim import SSDConfig
from repro.core.workload import (aging_stream, iter_request_chunks,
                                 overwrite_stream)


def _assert_translations_equal(th, ts):
    assert np.array_equal(th.op_cls, ts.op_cls)
    assert np.array_equal(th.arrival_us, ts.arrival_us)
    assert np.array_equal(th.payload, ts.payload)
    assert np.array_equal(th.request_id, ts.request_id)
    assert np.array_equal(th.gc, ts.gc)
    assert th.stats == ts.stats
    assert np.array_equal(th.state.l2p, ts.state.l2p)
    assert np.array_equal(th.state.p2l, ts.state.p2l)
    assert np.array_equal(th.state.valid_count, ts.state.valid_count)
    assert np.array_equal(th.state.full, ts.state.full)
    assert np.array_equal(th.state.fill_seq, ts.state.fill_seq)
    assert np.array_equal(th.state.erase_count, ts.state.erase_count)
    assert list(th.state.free) == list(ts.state.free)
    assert th.state.open_block == ts.state.open_block
    assert th.state.next_page == ts.state.next_page
    assert th.state._seq == ts.state._seq


# --- oracle agreement: the tentpole invariant -------------------------------


@pytest.mark.parametrize("policy", ftl.GC_POLICIES)
@pytest.mark.parametrize("blocks,ppb", [(16, 4), (32, 16), (64, 32)])
@pytest.mark.parametrize("op", [0.15, 0.28, 0.5])
def test_scan_matches_host_grid(policy, blocks, ppb, op):
    """Op-for-op agreement over policy × geometry × overprovisioning,
    with preconditioning (the ISSUE acceptance grid)."""
    spec = ftl.FTLSpec(blocks=blocks, pages_per_block=ppb,
                       overprovision=op, gc_policy=policy,
                       precondition=True)
    stream = overwrite_stream(200, 100, seed=3)
    try:
        th = translate_err = None
        th = ftl.translate(stream, spec)
    except RuntimeError as e:
        translate_err = str(e)
    if translate_err is not None:
        with pytest.raises(RuntimeError) as ei:
            ftl_scan.translate_scan(stream, spec)
        assert str(ei.value) == translate_err
        return
    _assert_translations_equal(th, ftl_scan.translate_scan(stream, spec))


@pytest.mark.parametrize("policy", ftl.GC_POLICIES)
def test_scan_matches_host_read_mix(policy):
    """Reads, Poisson arrivals and a skewed footprint exercise every
    branch of the machine (host reads never touch the map)."""
    spec = ftl.FTLSpec(blocks=64, pages_per_block=16, overprovision=0.25,
                       gc_policy=policy, precondition=True)
    stream = aging_stream(800, 600, read_fraction=0.3,
                          mean_interarrival_us=2.0, seed=11)
    _assert_translations_equal(ftl.translate(stream, spec),
                               ftl_scan.translate_scan(stream, spec))


@pytest.mark.parametrize("policy", ftl.GC_POLICIES)
def test_scan_chaining_matches_host(policy):
    """state= chains aging: scan→scan and host→scan both continue the
    drive exactly like host→host (stats stay cumulative)."""
    spec = ftl.FTLSpec(blocks=64, pages_per_block=16, overprovision=0.28,
                       gc_policy=policy, precondition=True)
    s1 = overwrite_stream(300, 120, seed=7)
    s2 = overwrite_stream(300, 120, seed=8)
    ref = ftl.translate(s2, spec, state=ftl.translate(s1, spec).state)
    ts1 = ftl_scan.translate_scan(s1, spec)
    _assert_translations_equal(
        ref, ftl_scan.translate_scan(s2, spec, state=ts1.state))
    _assert_translations_equal(
        ref, ftl_scan.translate_scan(
            s2, spec, state=ftl.translate(s1, spec).state))


def test_scan_error_messages_match_host():
    """Deferred error decode reproduces the host RuntimeErrors
    verbatim (the deadlock grid cell)."""
    spec = ftl.FTLSpec(blocks=8, pages_per_block=8, overprovision=0.15,
                       precondition=True)
    stream = overwrite_stream(64, 24, seed=3)
    with pytest.raises(RuntimeError) as host_err:
        ftl.translate(stream, spec)
    with pytest.raises(RuntimeError) as scan_err:
        ftl_scan.translate_scan(stream, spec)
    assert str(scan_err.value) == str(host_err.value)


def test_scan_rejects_faulty_state_and_bad_streams():
    spec = ftl.FTLSpec(blocks=32, pages_per_block=8, overprovision=0.3)
    st = ftl.FTLState(spec)
    st.bad[3] = True
    with pytest.raises(ValueError, match="fault-free"):
        ftl_scan.scan_state_from_host(st)
    s = overwrite_stream(4, 4)
    empty = dataclasses.replace(s, **{
        f.name: getattr(s, f.name)[:0]
        for f in dataclasses.fields(s)
        if isinstance(getattr(s, f.name), np.ndarray)})
    with pytest.raises(ValueError, match="empty workload"):
        ftl_scan.translate_scan(empty, spec)


def test_small_buffer_retry_converges():
    """An undersized output buffer is detected and doubled, not
    mis-translated: force a tiny t_max through the low-level runner."""
    spec = ftl.FTLSpec(blocks=32, pages_per_block=8, overprovision=0.3,
                       precondition=True)
    stream = overwrite_stream(256, 128, seed=5)
    th = ftl.translate(stream, spec)
    ts = ftl_scan.translate_scan(stream, spec)
    _assert_translations_equal(th, ts)
    # the public path already buckets; drive _run_machine directly with
    # a hint far below the emitted count to exercise the doubling loop
    from repro.core.workload import request_lpns, request_ops
    cls, arr, rid, pay = request_ops(stream)
    lpns = request_lpns(stream, spec.logical_pages)
    fs = ftl_scan.scan_state_from_host(ftl.FTLState(spec))
    fs, ys, runs = ftl_scan._run_machine(fs, spec, cls, arr, pay, rid,
                                         lpns, 1)
    assert int(np.sum(np.asarray(ys[-1]))) >= len(cls)
    assert runs > 1, "a hint of one step must double at least once"


# --- satellite: erase-count accounting --------------------------------------


def test_erase_counts_host_and_scan():
    """Per-block wear lands in FTLStats from both translators, covers
    the preconditioning phase, and sums to the erase ops ever emitted."""
    spec = ftl.FTLSpec(blocks=32, pages_per_block=8, overprovision=0.25,
                       precondition=True)
    stream = overwrite_stream(400, 150, seed=9)
    th = ftl.translate(stream, spec)
    ts = ftl_scan.translate_scan(stream, spec)
    assert th.stats.max_erase_count == ts.stats.max_erase_count
    assert th.stats.mean_erase_count == ts.stats.mean_erase_count
    assert np.array_equal(th.state.erase_count, ts.state.erase_count)
    # the measured window resets counters; lifetime wear keeps growing
    assert int(th.state.erase_count.sum()) >= th.stats.erases > 0
    assert th.stats.max_erase_count == int(th.state.erase_count.max())
    assert th.stats.mean_erase_count == pytest.approx(
        float(th.state.erase_count.mean()))
    fresh = ftl.translate(
        stream, dataclasses.replace(spec, precondition=False))
    assert int(fresh.state.erase_count.sum()) == fresh.stats.erases


# --- the API surface: default path, sweep, streaming ------------------------


def _sim(channels=2, ways=4):
    return api.Simulator(SSDConfig(cell=CellType.MLC, channels=channels,
                                   ways=ways))


SPEC = ftl.FTLSpec(blocks=64, pages_per_block=32, overprovision=0.25,
                   precondition=True)


def test_run_default_path_is_scan(monkeypatch):
    """Fault-free FTL queries ride the compiled engine; block-level
    fault probabilities fall back to the host oracle."""
    sim = _sim()
    stream = overwrite_stream(400, 300, seed=2)
    calls = {"scan": 0, "host": 0}
    orig_scan, orig_host = ftl_scan.translate_scan, ftl.translate

    def spy_scan(*a, **kw):
        calls["scan"] += 1
        return orig_scan(*a, **kw)

    def spy_host(*a, **kw):
        calls["host"] += 1
        return orig_host(*a, **kw)

    import repro.core.api as core_api
    monkeypatch.setattr(core_api._ftl_scan, "translate_scan", spy_scan)
    monkeypatch.setattr(core_api._ftl, "translate", spy_host)
    sim.run(stream, ftl=SPEC)
    assert calls == {"scan": 1, "host": 0}
    sim.run(stream,
            ftl=dataclasses.replace(SPEC, overprovision=0.5,
                                    precondition=False),
            faults=api.FaultSpec(prog_fail_prob=0.002, seed=3))
    assert calls == {"scan": 1, "host": 1}
    # per-op surcharges alone (retry/jitter) stay on the scan path
    sim.run(stream, ftl=SPEC, faults=api.FaultSpec(wear=0.5, seed=3))
    assert calls == {"scan": 2, "host": 1}


@pytest.mark.parametrize("engine", ["scan", "prefix", "pallas",
                                    "streaming", "oracle"])
def test_engines_agree_scan_vs_host_translation(engine, monkeypatch):
    """ISSUE acceptance: every ftl-capable engine answers the scan
    -translated stream within 1e-3 of the host-translated one (they
    are op-for-op equal, so the ends are bitwise equal)."""
    sim = _sim()
    stream = overwrite_stream(600, 450, read_fraction=0.2, seed=4)
    scan_res = sim.run(stream, ftl=SPEC, engine=engine)
    import repro.core.api as core_api
    monkeypatch.setattr(
        core_api._ftl_scan, "translate_scan",
        lambda s, sp, **kw: ftl.translate(s, sp, **kw))
    host_res = sim.run(stream, ftl=SPEC, engine=engine)
    assert scan_res.end_us == host_res.end_us
    assert scan_res.waf == host_res.waf
    assert scan_res.ftl_stats == host_res.ftl_stats
    assert scan_res.n_ops == host_res.n_ops


def test_sweep_ftl_matches_per_point_runs():
    """The fused vmap sweep answers within the 1e-3 cross-engine
    contract of the serial run(SimRequest(ftl=...)) path — the op
    sequence is identical by the oracle gate; the end time is the
    sweep's masked prefix fold vs run()'s scan engine."""
    sim = _sim()
    stream = overwrite_stream(300, 150, seed=5)
    specs = [dataclasses.replace(SPEC, blocks=64, pages_per_block=16,
                                 overprovision=op, gc_policy=pol)
             for op in (0.15, 0.3, 0.5) for pol in ftl.GC_POLICIES]
    ends = sim.sweep(None, stream, ftl=specs)
    assert ends.shape == (len(specs),)
    for i, s in enumerate(specs):
        ref = sim.run(stream, ftl=s).end_us
        assert abs(ends[i] - ref) / ref < 1e-3, (s, ends[i], ref)
    # WAF ordering sanity across the OP axis (greedy points)
    greedy = [sim.run(stream, ftl=s).waf for s in specs[::2]]
    assert greedy[0] > greedy[1] > greedy[2]


def test_sweep_ftl_validation():
    sim = _sim()
    stream = overwrite_stream(64, 32, seed=1)
    with pytest.raises(ValueError, match="tables must be"):
        sim.sweep([sim.table], stream, ftl=[SPEC])
    with pytest.raises(ValueError, match="share geometry"):
        sim.sweep(None, stream, ftl=[
            SPEC, dataclasses.replace(SPEC, blocks=32)])
    with pytest.raises(ValueError, match="dynamic"):
        sim.sweep(None, stream, ftl=[SPEC], sched_policy="least_loaded")
    with pytest.raises(ValueError, match="at least one"):
        sim.sweep(None, stream, ftl=[])


def test_sweep_ftl_error_decode():
    """A deadlocked lane raises the host message for its own spec."""
    sim = _sim()
    stream = overwrite_stream(64, 24, seed=3)
    bad = ftl.FTLSpec(blocks=8, pages_per_block=8, overprovision=0.15,
                      precondition=True)
    with pytest.raises(RuntimeError, match="fully valid"):
        sim.sweep(None, stream, ftl=[bad])


def test_run_stream_ftl_matches_one_shot():
    """Chunked translation + chunk lowering + streaming fold equals
    the one-shot FTL run bit-for-bit (end, WAF, stats)."""
    sim = _sim()
    spec = dataclasses.replace(SPEC, blocks=64, pages_per_block=16,
                               overprovision=0.28)
    stream = overwrite_stream(500, 200, seed=6)
    one = sim.run(stream, ftl=spec)
    for chunk in (64, 128, 500):
        res = sim.run_stream(iter_request_chunks(stream, chunk),
                             ftl=spec)
        assert res.end_us == one.end_us, chunk
        assert res.waf == one.waf
        assert res.ftl_stats == one.ftl_stats
        assert res.n_ops == one.n_ops
        assert res.payload_bytes == one.payload_bytes


def test_run_stream_ftl_faults_composition():
    """FTL × faults × chunked streaming: the sequential fault sampler
    makes the chunked surcharges identical to the one-shot ones."""
    sim = _sim()
    spec = dataclasses.replace(SPEC, blocks=64, pages_per_block=16,
                               overprovision=0.3)
    faults = api.FaultSpec(wear=0.6, jitter_us=0.4, seed=13)
    stream = overwrite_stream(400, 160, seed=7)
    one = sim.run(stream, ftl=spec, faults=faults)
    res = sim.run_stream(iter_request_chunks(stream, 96), ftl=spec,
                         faults=faults)
    assert res.end_us == one.end_us
    assert res.waf == one.waf
    # chunk-size invariance of the whole composition
    res2 = sim.run_stream(iter_request_chunks(stream, 37), ftl=spec,
                          faults=faults)
    assert res2.end_us == res.end_us


def test_run_stream_ftl_validation():
    sim = _sim()
    stream = overwrite_stream(64, 32, seed=1)
    with pytest.raises(ValueError, match="needs ftl="):
        sim.run_stream(iter([]), faults=api.FaultSpec(wear=0.5))
    with pytest.raises(ValueError, match="dynamic"):
        sim.run_stream(iter_request_chunks(stream, 32), ftl=SPEC,
                       sched_policy="least_loaded")
    with pytest.raises(ValueError, match="one-shot"):
        sim.run_stream(iter_request_chunks(stream, 32), ftl=SPEC,
                       faults=api.FaultSpec(prog_fail_prob=0.1))
    with pytest.raises(ValueError, match="empty workload"):
        sim.run_stream(iter([]), ftl=SPEC)


# --- satellite: chunked lowering exactness ----------------------------------


@pytest.mark.parametrize("policy", sched.STATIC_POLICIES)
def test_lower_ops_chunk_matches_lower_ops(policy):
    rng = np.random.default_rng(2)
    n, C, W = 317, 4, 2
    cls = rng.integers(2, 7, n).astype(np.int32)
    arr = np.sort(rng.random(n)).astype(np.float32)
    pay = rng.random(n) < 0.7
    one = sched.lower_ops(cls, arr, C, W, policy, pay)
    off, parts = 0, []
    for lo in range(0, n, 60):
        tr, off = sched.lower_ops_chunk(
            cls[lo:lo + 60], arr[lo:lo + 60], C, W, policy,
            pay[lo:lo + 60], off)
        parts.append(tr)
    assert off == n
    for f in ("cls", "channel", "way", "parity"):
        assert np.array_equal(
            np.asarray(getattr(one, f)),
            np.concatenate([np.asarray(getattr(t, f)) for t in parts])), f
    with pytest.raises(ValueError, match="dynamic"):
        sched.lower_ops_chunk(cls, arr, C, W, "least_loaded")


# --- satellite: lru WAF under skew ------------------------------------------


def test_lru_waf_under_skew():
    """Under a hot/cold skew, LRU's oldest-block victims carry the cold
    (still-valid) data, so LRU relocates at least as much as greedy;
    both sit in the analytic neighbourhood for the utilization."""
    spec_g = ftl.FTLSpec(blocks=64, pages_per_block=16,
                         overprovision=0.28, gc_policy="greedy",
                         precondition=True)
    spec_l = dataclasses.replace(spec_g, gc_policy="lru")
    stream = aging_stream(6000, 700, hot_fraction=0.2, hot_traffic=0.8,
                          seed=17)
    waf_g = ftl_scan.translate_scan(stream, spec_g).stats.waf
    waf_l = ftl_scan.translate_scan(stream, spec_l).stats.waf
    assert waf_l >= waf_g > 1.0
    # regression band: pinned against the host translator's values
    assert waf_g == pytest.approx(
        ftl.translate(stream, spec_g).stats.waf)
    assert waf_l == pytest.approx(
        ftl.translate(stream, spec_l).stats.waf)
    assert 1.0 < waf_l < 3.0 * ftl.analytic_waf(spec_l.utilization)


# --- satellite: FTL sub-session cache ---------------------------------------


def test_ftl_session_cache_lru_eviction():
    """The sub-session cache is LRU-bounded with CacheInfo counters:
    the oldest timing key is evicted past max_ftl_sessions, and a
    rebuilt session still answers identically."""
    sim = api.Simulator(SSDConfig(channels=2, ways=2),
                        max_ftl_sessions=2)
    stream = overwrite_stream(120, 60, seed=1)
    spec = ftl.FTLSpec(blocks=32, pages_per_block=8, overprovision=0.3)
    specs = [dataclasses.replace(spec, map_us=m)
             for m in (0.5, 0.7, 0.9)]
    first = sim.run(stream, ftl=specs[0]).end_us
    info0 = sim.ftl_cache_info()
    assert info0.entries == 1 and info0.max_entries == 2
    sim.run(stream, ftl=specs[1])
    sim.run(stream, ftl=specs[2])            # evicts specs[0]'s session
    info = sim.ftl_cache_info()
    assert info.entries == 2 and info.evictions == 1
    assert sim.run(stream, ftl=specs[0]).end_us == first   # rebuilt
    assert sim.ftl_cache_info().evictions == 2
    sim.run(stream, ftl=specs[0])                          # now a hit
    assert sim.ftl_cache_info().hits >= 1
    with pytest.raises(ValueError, match="max_ftl_sessions"):
        api.Simulator(SSDConfig(channels=2, ways=2), max_ftl_sessions=0)


def test_ftl_session_memoised_identity_preserved():
    """Same timing key → same sibling session object (the behaviour the
    pre-LRU dict gave); different map_us → different session."""
    sim = _sim()
    a = sim._ftl_session(SPEC)
    b = sim._ftl_session(dataclasses.replace(SPEC, overprovision=0.4))
    c = sim._ftl_session(dataclasses.replace(SPEC, map_us=2.5))
    assert a is b and a is not c
