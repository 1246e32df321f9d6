"""The simulator's own spans (``repro.core.spans``), on the CPU.

Queries run under ``jax.profiler``; the trace must hold the ``ssd.*``
spans nested as the query path opens them, with their stats, and the
program names that trace readers match must stay what they are.
"""

import dataclasses
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.api as api
from repro.core import ftl, ftl_scan
from repro.core import sim as _sim
from repro.core import trace as tr
from repro.core.api import _bucket_len, _padded_trace_args, _trace_args
from repro.core.nand import CellType
from repro.core.sim import SSDConfig
from repro.core.spans import PREFIX
from repro.core.workload import (jesd219_stream, overwrite_stream,
                                 poisson_stream, request_partial)
from repro.kernels.scanfold import ops as scanfold_ops

SPEC = ftl.FTLSpec(blocks=32, pages_per_block=8, overprovision=0.25,
                   precondition=True)


def _sim_for():
    return api.Simulator(SSDConfig(cell=CellType.SLC, channels=2, ways=4))


def traced(tmp_path, fn):
    """(fn's answer, [(name, start, end, stats)] of its ``ssd.`` spans,
    sorted by start, outer first)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    from jax.profiler import ProfileData
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for plane in ProfileData.from_file(path[-1]).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(PREFIX)]
    return out, sorted(spans, key=lambda s: (s[1], -s[2]))


def named(spans, name):
    return [s for s in spans if s[0] == name]


def inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_aged_run_spans_nest_with_stats(tmp_path):
    sim = _sim_for()
    stream = overwrite_stream(300, 150, seed=4)
    sim.run(stream, ftl=SPEC)                       # compile outside
    res, spans = traced(tmp_path, lambda: sim.run(stream, ftl=SPEC))
    (root,) = named(spans, "ssd.run")
    assert root[3]["ops"] == 300 and root[3]["points"] == 1
    assert root[3]["call"] == 2, "the session's second call"
    assert all(inside(s, root) for s in spans)
    (trans,) = named(spans, "ssd.translate")
    (pre,) = named(spans, "ssd.precondition")
    assert inside(pre, trans)
    assert pre[3]["writes"] == len(ftl.precondition_lpns(SPEC))
    assert pre[3]["machine_runs"] >= 1
    assert trans[3]["host_ops"] == 300
    assert trans[3]["out_ops"] == res.n_ops
    assert trans[3]["machine_runs"] == pre[3]["machine_runs"] + 1
    # the aged fold with completions, then the fresh fold of host ops
    folds = named(spans, "ssd.fold")
    assert len(folds) == 2 and all(s[3]["engine"] == "scan" for s in folds)
    assert folds[0][3]["ops"] == res.n_ops
    assert folds[0][3]["steps"] == _bucket_len(res.n_ops)
    assert folds[1][3]["ops"] == 300
    assert all(f[1] >= trans[2] for f in folds), "folds after translation"
    stages = named(spans, "ssd.stage")
    assert [s[3]["steps"] for s in stages] == [f[3]["steps"] for f in folds]
    for st, fo in zip(stages, folds):
        assert st[2] <= fo[1], "arguments are staged before the fold"
    lowers = named(spans, "ssd.lower")
    assert [s[3]["ops"] for s in lowers] == [res.n_ops, 300]
    assert named(spans, "ssd.extract")
    # nothing overlaps but by nesting
    for a in spans:
        for b in spans:
            if a is not b and a[1] < b[1] < a[2]:
                assert b[2] <= a[2], (a[0], b[0])


def test_short_estimate_counts_every_machine_run(tmp_path, monkeypatch):
    sim = _sim_for()
    stream = overwrite_stream(300, 150, seed=4)
    monkeypatch.setattr(ftl_scan, "estimate_t_max",
                        lambda *a, **k: 8)
    want = sim.run(stream, ftl=SPEC)
    res, spans = traced(tmp_path, lambda: sim.run(stream, ftl=SPEC))
    (trans,) = named(spans, "ssd.translate")
    (pre,) = named(spans, "ssd.precondition")
    assert pre[3]["machine_runs"] > 1
    assert trans[3]["machine_runs"] > pre[3]["machine_runs"] + 1
    assert res.end_us == want.end_us


def test_sector_stream_translate_span_carries_rmw_counters(tmp_path):
    """A sector-addressed stream's ``ssd.translate`` span carries the
    RMW reads, partial pages and host sectors, equal to ``FTLStats``."""
    sim = _sim_for()
    spec = dataclasses.replace(SPEC, sectors_per_page=4)
    stream = jesd219_stream(120, spec.logical_pages, 4, seed=6)
    sim.run(stream, ftl=spec)
    res, spans = traced(tmp_path, lambda: sim.run(stream, ftl=spec))
    (trans,) = named(spans, "ssd.translate")
    st = trans[3]
    assert st["rmw_reads"] == res.ftl_stats.rmw_reads > 0
    assert st["host_sectors"] * 512 == res.ftl_stats.host_bytes_written
    assert st["host_sectors"] == int(np.sum(stream.n_sectors))
    assert st["partial_pages"] == int(request_partial(stream, 4).sum())
    assert st["host_ops"] == stream.total_pages
    assert st["out_ops"] == res.n_ops


def test_workload_run_spans(tmp_path):
    sim = _sim_for()
    stream = poisson_stream(500, 5.0, read_fraction=0.7, seed=1)
    sim.run(stream)
    res, spans = traced(tmp_path, lambda: sim.run(stream))
    (root,) = named(spans, "ssd.run")
    assert root[3]["ops"] == 500
    assert [s[0] for s in spans] == ["ssd.run", "ssd.lower", "ssd.stage",
                                     "ssd.fold", "ssd.extract",
                                     "ssd.extract"]
    assert all(inside(s, root) for s in spans)
    fold = named(spans, "ssd.fold")[0][3]
    assert (fold["ops"], fold["steps"]) == (res.n_ops,
                                            _bucket_len(res.n_ops))
    assert not named(spans, "ssd.translate")


@pytest.mark.parametrize("engine", ["prefix", "scan"])
def test_sweep_spans(tmp_path, engine):
    sim = _sim_for()
    trace = tr.mixed_trace(256, 2, 4, read_fraction=0.7, seed=2)
    tables = [tr.op_class_table(SSDConfig(cell=c, channels=2, ways=4))
              for c in (CellType.SLC, CellType.MLC)]
    sim.sweep(tables, trace, engine=engine)
    end, spans = traced(
        tmp_path, lambda: sim.sweep(tables, trace, engine=engine))
    assert [s[0] for s in spans] == ["ssd.sweep", "ssd.stage", "ssd.fold"]
    root, stage, fold = spans
    assert root[3] == {"call": 2, "ops": 256, "points": 2}
    assert stage[3] == {"steps": 256}
    assert fold[3] == {"engine": engine, "ops": 256, "steps": 256,
                       "points": 2}
    assert inside(stage, root) and inside(fold, root) and stage[2] <= fold[1]
    assert end.shape == (2,)


@pytest.mark.parametrize("query", ["poisson", "aged"])
@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_scan_folds_route_by_backend(tmp_path, monkeypatch, backend, query):
    """On a TPU backend the scan engine's folds run the Pallas kernel
    (interpreted here) over the true op count, and each ``ssd.fold``
    says ``kernel=1``; elsewhere they run the masked ``lax.scan`` over
    the bucket, ``kernel=0``.  The answers are identical."""
    sim = _sim_for()
    if query == "poisson":
        stream, kw = poisson_stream(500, 5.0, read_fraction=0.7, seed=1), {}
    else:
        stream, kw = overwrite_stream(300, 150, seed=4), {"ftl": SPEC}
    want = sim.run(stream, **kw)
    if backend == "tpu":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(scanfold_ops, "resolve_interpret",
                            lambda interpret: True)
    res, spans = traced(tmp_path, lambda: sim.run(stream, **kw))
    folds = named(spans, "ssd.fold")
    assert len(folds) == (1 if query == "poisson" else 2)
    on_tpu = backend == "tpu"
    for f in folds:
        assert f[3]["engine"] == "scan" and f[3]["kernel"] == int(on_tpu)
        assert f[3]["steps"] == (f[3]["ops"] if on_tpu
                                 else _bucket_len(f[3]["ops"]))
    assert res.end_us == want.end_us
    np.testing.assert_array_equal(res.request_lat_us, want.request_lat_us)


def hlo_module(fn, *args, **kw) -> str:
    text = fn.lower(*args, **kw).compile().as_text()
    return text.split("HloModule ", 1)[1].split(",", 1)[0].split()[0]


def test_trace_readers_program_names_are_pinned():
    """Trace readers find the folds by these names: a rename would zero
    a translation reading or move the fold's."""
    sim = _sim_for()
    t = tr.mixed_trace(64, 2, 4, read_fraction=0.5, seed=7)
    padded = _padded_trace_args(t, 64)
    assert hlo_module(_sim.trace_completions_masked, *sim._targs, *padded,
                      n_channels=2, batched=False) \
        == "jit_trace_completions_masked"
    assert hlo_module(_sim.trace_end_time_masked, *sim._targs, *padded,
                      n_channels=2, batched=False) \
        == "jit_trace_end_time_masked"
    n = jnp.int32(t.n_ops)
    assert hlo_module(scanfold_ops.scan_fold_completions, *sim._targs,
                      *padded[:-1], n, n_channels=2, batched=False) \
        == "jit_scan_fold_completions"
    assert hlo_module(scanfold_ops.scan_fold_end_time, *sim._targs,
                      *padded[:-1], n, n_channels=2, batched=False) \
        == "jit_scan_fold_end_time"
    stacked = tuple(x[None] for x in sim._targs)
    assert hlo_module(_sim.trace_end_time_prefix_batch, *stacked,
                      *_trace_args(t), n_channels=2, n_ways=4,
                      batched=False, segment_len=64) \
        == "jit_trace_end_time_prefix_batch"
    spec = ftl.FTLSpec(blocks=8, pages_per_block=8, overprovision=0.5)
    fold = ftl_scan._jitted_fold(spec.blocks, spec.pages_per_block, 64, 64)
    z = np.zeros(64)
    assert hlo_module(
        fold, jnp.asarray(z, jnp.int32), jnp.asarray(z, jnp.float32),
        jnp.asarray(z, bool), jnp.asarray(z, jnp.int32),
        jnp.asarray(z, jnp.int32), np.int32(0),
        np.int32(spec.gc_free_blocks), False,
        ftl_scan.scan_state_fresh(spec)) == "jit_fold"
