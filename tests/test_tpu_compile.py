"""The Pallas kernels of the main path compile for a TPU v5e chip.

Each test lowers and compiles one kernel ahead of time for a described
``v5e:2x2`` topology (no chip attached) with ``interpret=False``: the
(max,+) kernels at the shapes ``chip_smoke.py`` runs on the chip, the
scan engine's fold kernel at the benchmark cells' shapes.  What the chip's compiler
refuses — an unsupported primitive, a misaligned reshape, a block over
the scoped VMEM limit — fails here without chip time.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.maxplus.kernel import (maxplus_fold_kernel,
                                          maxplus_fold_many_kernel)
from repro.kernels.scanfold import scan_fold_completions, scan_fold_end_time


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back without
    # one: keep them out of any persistent cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


F32, I32 = jnp.float32, jnp.int32

# chip_smoke.py shapes: the periodic channel kernel at 1x16 (12 design
# points, state rows N = 20, period 16, 4096 pages); the trace-indexed
# kernel at 2x8 (6 points, N = 22, 64 combos, 16,384 ops); the
# megakernel at 2x8 (128 traces of 4096 ops, 64 combos + identity)
PER_B, PER_M, PER_N, PER_T = 12, 16, 20, 4096
IDX_B, IDX_M, IDX_N, IDX_T = 6, 64, 22, 16_384
MANY_B, MANY_M1, MANY_N, MANY_T = 128, 65, 22, 4096
PHASES = 4
# the scan engine's fold kernel at the benchmark cells' shapes, 4
# channels x 16 ways: 65,536 ops under the paper's 2-class table
# (loaded_latency) and a 262,144-op bucket under the FTL's 7 classes
# (the aged drive's fold), and the 2^23-op bucket of JESD219A's enterprise
# workload on the aged drive (read-modify-write, GC and all)
SCAN_CASES = {"paper_65536": (65_536, 2), "ftl_262144": (262_144, 7),
              "ftl_8388608": (8_388_608, 7)}


@pytest.mark.parametrize("energy", [False, True], ids=["end", "energy"])
def test_periodic_kernel_compiles(one_chip, energy):
    shapes = [((PER_B, PER_M, PER_N, PER_N), F32), ((PER_B, PER_N), F32)]
    if energy:
        shapes.append(((PER_B, PER_M, PHASES), F32))

    def fn(mats, s0, *e):
        return maxplus_fold_kernel(mats, s0, t_steps=PER_T,
                                   energy=e[0] if e else None,
                                   interpret=False)

    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("energy", [False, True], ids=["end", "energy"])
def test_trace_indexed_kernel_compiles(one_chip, energy):
    shapes = [((IDX_B, IDX_M, IDX_N, IDX_N), F32), ((IDX_B, IDX_N), F32),
              ((IDX_T,), I32), ((IDX_T,), F32), ((IDX_B, IDX_M, IDX_N), F32),
              ((IDX_T,), F32), ((IDX_B, IDX_M, IDX_N), F32)]
    if energy:
        shapes.append(((IDX_B, IDX_M, PHASES), F32))

    def fn(mats, s0, idx, arr, gvec, ext, wvec, *e):
        return maxplus_fold_kernel(mats, s0, t_steps=IDX_T, idx=idx,
                                   arrivals=arr, gvec=gvec, extras=ext,
                                   wvec=wvec, energy=e[0] if e else None,
                                   interpret=False)

    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("faults", [False, True], ids=["clean", "faults"])
def test_megakernel_compiles(one_chip, faults):
    shapes = [((MANY_M1, MANY_N, MANY_N), F32), ((MANY_M1, MANY_N), F32),
              ((MANY_B, MANY_T), I32), ((MANY_B, MANY_T), F32),
              ((MANY_N,), F32), ((MANY_B,), I32)]
    if faults:
        shapes += [((MANY_B, MANY_T), F32), ((MANY_M1, MANY_N), F32)]

    def fn(mats, gvec, idx, arr, s0, lengths, *fw):
        return maxplus_fold_many_kernel(
            mats, gvec, idx, arr, s0, lengths,
            extras=fw[0] if fw else None, wvec=fw[1] if fw else None,
            interpret=False)

    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("completions", [False, True],
                         ids=["end", "completions"])
@pytest.mark.parametrize("batched", [False, True], ids=["eager", "batched"])
@pytest.mark.parametrize("case", sorted(SCAN_CASES))
def test_scan_fold_kernel_compiles(one_chip, case, batched, completions):
    t, k = SCAN_CASES[case]
    shapes = ([((k,), F32)] * 7 + [((t,), I32)] * 4 + [((t,), F32)] * 2
              + [((), I32)])
    fold = scan_fold_completions if completions else scan_fold_end_time

    def fn(*args):
        return fold(*args, n_channels=4, batched=batched, interpret=False)

    _compile(fn, one_chip, *shapes)


def test_dictionary_beyond_vmem_raises():
    """A 4x16 trace-indexed dictionary (250 combos, N = 74) cannot fit
    VMEM; the kernel refuses when it is traced, before any compile."""
    m, n, t = 250, 74, 1024
    args = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((1, m, n, n), F32), ((1, n), F32), ((t,), I32))]
    with pytest.raises(ValueError, match="VMEM_LIMIT_BYTES"):
        jax.eval_shape(lambda mats, s0, idx: maxplus_fold_kernel(
            mats, s0, t_steps=t, idx=idx, interpret=False), *args)
