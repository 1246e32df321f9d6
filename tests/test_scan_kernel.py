"""The scan engine's Pallas fold (``repro.kernels.scanfold``) against the
masked ``lax.scan`` folds it replaces on a TPU.

The kernel runs in interpret mode here.  It performs the scan step's
f32 operations in the same order, so end times and completions must be
*equal*, not close: ``np.testing.assert_array_equal`` throughout.
Completions past ``n_ops`` are garbage on both sides and not compared.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ftl, sched
from repro.core import sim as _sim
from repro.core import trace as tr
from repro.core.api import _TABLE_FIELDS, _pad_trace_np
from repro.core.nand import CellType
from repro.core.sim import SSDConfig
from repro.core.sim_ref import simulate_trace_completions_ref
from repro.core.workload import overwrite_stream
from repro.kernels.scanfold import scan_fold_completions, scan_fold_end_time

GEOMETRIES = [(c, w) for c in (1, 2, 4) for w in (1, 4, 16)]
# (bucket, n_ops): a full bucket over two time tiles, and an op count
# well below its bucket that still crosses a tile boundary
LOADS = {"back_to_back": (2048, 2048), "arrivals_faults": (4096, 1500)}


def _targs(table):
    return tuple(jnp.asarray(getattr(table, f)) for f in _TABLE_FIELDS)


def _with_load(trace, load, seed):
    """The trace with zero (``back_to_back``) or random sorted arrivals
    and sparse retry surcharges (``arrivals_faults``)."""
    if load == "back_to_back":
        return trace
    rng = np.random.default_rng(seed)
    n = trace.n_ops
    arr = np.cumsum(rng.exponential(4.0, n)).astype(np.float32)
    ext = (rng.uniform(0.0, 40.0, n) * (rng.random(n) < 0.15)).astype(
        np.float32)
    return tr.OpTrace(trace.cls, trace.channel, trace.way, trace.parity,
                      trace.channels, trace.ways, arrival_us=arr,
                      extra_us=ext)


def _both(targs, trace, bucket, batched):
    """((end, completions) of the masked scans, the same of the kernel),
    completions cut to the trace's ops."""
    *ops, valid = (jnp.asarray(x) for x in _pad_trace_np(trace, bucket))
    kw = dict(n_channels=trace.channels, batched=batched)
    n = trace.n_ops
    s_end, s_comp = _sim.trace_completions_masked(*targs, *ops, valid, **kw)
    s_end2 = _sim.trace_end_time_masked(*targs, *ops, valid, **kw)
    assert float(s_end2) == float(s_end)
    k_end, k_comp = scan_fold_completions(*targs, *ops, n, **kw)
    k_end2 = scan_fold_end_time(*targs, *ops, n, **kw)
    return ((np.asarray(s_end), np.asarray(s_comp)[:n]),
            (np.asarray(k_end), np.asarray(k_comp)[:n], np.asarray(k_end2)))


def _assert_identical(scan, kernel):
    (s_end, s_comp), (k_end, k_comp, k_end2) = scan, kernel
    np.testing.assert_array_equal(k_end, s_end)
    np.testing.assert_array_equal(k_end2, s_end)
    np.testing.assert_array_equal(k_comp, s_comp)


@pytest.mark.parametrize("load", sorted(LOADS))
@pytest.mark.parametrize("policy", ["eager", "batched"])
@pytest.mark.parametrize("channels,ways", GEOMETRIES,
                         ids=[f"{c}x{w}" for c, w in GEOMETRIES])
def test_kernel_matches_masked_scan(channels, ways, policy, load):
    bucket, n = LOADS[load]
    cfg = SSDConfig(cell=CellType.MLC, channels=channels, ways=ways)
    trace = _with_load(tr.mixed_trace(n, channels, ways, read_fraction=0.6,
                                      seed=channels * 17 + ways), load,
                       seed=ways)
    _assert_identical(*_both(_targs(tr.op_class_table(cfg)), trace, bucket,
                             policy == "batched"))


@pytest.mark.parametrize("policy", ["eager", "batched"])
def test_kernel_matches_masked_scan_on_ftl_table(policy):
    """The 7-class table of the FTL stage (reads, writes, GC copies and
    erases) on a GC-heavy translated overwrite stream."""
    cfg = SSDConfig(cell=CellType.SLC, channels=4, ways=4)
    spec = ftl.FTLSpec(blocks=16, pages_per_block=8, overprovision=0.25)
    table = ftl.ftl_op_class_table(cfg, spec)
    assert table.n_classes == 7
    t = ftl.translate(overwrite_stream(600, 80, seed=5), spec)
    trace = sched.lower_ops(t.op_cls, t.arrival_us, cfg.channels, cfg.ways,
                            payload=t.payload)
    assert len(np.unique(trace.cls)) >= 4 and trace.n_ops > 1024
    _assert_identical(*_both(_targs(table), trace, 4096,
                             policy == "batched"))


def test_kernel_matches_event_loop_oracle():
    """Against the plain-Python event loop (float64, so within f32
    rounding)."""
    cfg = SSDConfig(cell=CellType.MLC, channels=2, ways=4)
    table = tr.op_class_table(cfg)
    trace = _with_load(tr.mixed_trace(700, 2, 4, read_fraction=0.5, seed=3),
                       "arrivals_faults", seed=3)
    *ops, _ = (jnp.asarray(x) for x in _pad_trace_np(trace, 1024))
    end, comp = scan_fold_completions(*_targs(table), *ops, trace.n_ops,
                                      n_channels=2, batched=False)
    ref_end, ref_comp = simulate_trace_completions_ref(table, trace)
    np.testing.assert_allclose(float(end), ref_end, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(comp)[: trace.n_ops], ref_comp,
                               rtol=1e-5)
