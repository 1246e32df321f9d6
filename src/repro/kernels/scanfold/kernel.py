"""The scan engine's per-op fold as one Pallas TPU kernel.

``repro.core.sim._trace_step_fn`` advances a few dozen scalars per page
op: ``bus_free[c]``, ``chip_free[c, w]``, ``ctrl_free`` and
``round_start[c]``.  As an XLA ``while`` loop every iteration runs a
string of tiny programs (input slices, table gathers, state updates,
the mask select, the output write) and costs ~20 µs on a v5e.  This
kernel runs the same recurrence for a whole trace in one launch on the
scalar unit:

* per-op operands arrive in SMEM one ``TIME_TILE`` block at a time
  over a sequential grid axis: the op word ``cls | channel << 8 |
  way << 16 | (parity & 1) << 24`` (``pack_ops``), the arrival
  and the reliability surcharge;
* the op-class table, its 7 timing columns flattened column-major, is
  one small SMEM block;
* the state lives in an SMEM output block that stays resident across
  the grid, laid out ``[bus_free (C) | chip_free (C x MAX_WAYS) |
  ctrl_free | round_start (C)]``;
* the op count is a scalar-prefetch operand: each time block folds
  ``min(TIME_TILE, n_ops - k * TIME_TILE)`` ops, and blocks past the
  last op neither fold nor fetch (their block index is clamped), so
  padding to a length bucket costs nothing.

Each step performs the f32 operations of ``_trace_step_fn`` in the same
order, so answers are bit-identical to ``trace_completions_masked`` /
``trace_end_time_masked`` over the same padded bucket.  Completions
past ``n_ops`` are left unwritten: trailing garbage the caller slices
off, as with the masked scans.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sim import MAX_WAYS
from repro.kernels.maxplus.kernel import TIME_TILE

#: Bits of each field of the packed op word (class, channel, way); the
#: page parity's low bit sits above them.
FIELD_BITS = 8
FIELD_MASK = (1 << FIELD_BITS) - 1

#: Table columns, in the order of ``repro.core.api._TABLE_FIELDS``.
CMD, PRE, SLOT, POST_LO, POST_HI, CTRL, ARB = range(7)


def pack_ops(cls, channel, way, parity, n_classes: int) -> jax.Array:
    """[T] int32 op words: the class (clamped to the table, as the
    scan's gather clamps it), channel, way and the parity's low bit."""
    cls = jnp.clip(cls.astype(jnp.int32), 0, n_classes - 1)
    return (cls | channel.astype(jnp.int32) << FIELD_BITS
            | way.astype(jnp.int32) << 2 * FIELD_BITS
            | (parity.astype(jnp.int32) & 1) << 3 * FIELD_BITS)


def _kernel(n_ref, tab_ref, op_ref, arr_ref, ext_ref, state_ref, *comp,
            n_channels: int, kp: int, batched: bool):
    """One time block of the fold (see the module docstring)."""
    chip0 = n_channels
    ctrl_at = n_channels * (MAX_WAYS + 1)
    round0 = ctrl_at + 1
    k = pl.program_id(0)
    tt = op_ref.shape[0]

    @pl.when(k == 0)
    def _():
        for i in range(state_ref.shape[0]):
            state_ref[i] = jnp.float32(0.0)

    def step(t, ctrl_free):
        op = op_ref[t]
        cl = op & FIELD_MASK
        c = (op >> FIELD_BITS) & FIELD_MASK
        w = (op >> 2 * FIELD_BITS) & FIELD_MASK
        odd = op >> 3 * FIELD_BITS
        arr = arr_ref[t]
        cmd = tab_ref[CMD * kp + cl]
        bus = state_ref[c]
        chip_at = chip0 + c * MAX_WAYS + w
        # round_start[c] takes bus_free[c] when a round opens (way 0)
        rs = jnp.where(w == 0, bus, state_ref[round0 + c])
        state_ref[round0 + c] = rs
        if batched:
            base = jnp.maximum(rs, arr)
            ready = (base + (w + 1).astype(jnp.float32) * cmd
                     + tab_ref[PRE * kp + cl])
        else:
            base = jnp.maximum(state_ref[chip_at], arr)
            ready = base + cmd + tab_ref[PRE * kp + cl]
        start = (jnp.maximum(jnp.maximum(bus, ready), ctrl_free)
                 + tab_ref[ARB * kp + cl])
        new_bus = start + tab_ref[SLOT * kp + cl]
        post = tab_ref[(POST_LO + odd) * kp + cl]
        state_ref[c] = new_bus
        done = new_bus + post + ext_ref[t]
        state_ref[chip_at] = done
        if comp:
            comp[0][t] = done
        return start + tab_ref[CTRL * kp + cl]

    n = jnp.minimum(tt, n_ref[0] - k * tt)
    state_ref[ctrl_at] = jax.lax.fori_loop(0, n, step, state_ref[ctrl_at])


def scan_fold(table, ops, arrival, extra, n_ops, *, n_channels: int,
              batched: bool, completions: bool, interpret: bool):
    """(state [C * (MAX_WAYS + 2) + 1], completions [T] or None) after
    folding the first ``n_ops`` ops.  ``table`` is [7, K] float32,
    ``ops`` the packed [T] int32 op words, ``arrival``/``extra`` [T]
    float32, ``n_ops`` an int32 scalar."""
    k = table.shape[1]
    kp = -(-k // 8) * 8
    t = ops.shape[0]
    tt = min(TIME_TILE, t)
    tp = -(-t // tt) * tt
    pad = tp - t
    tab = jnp.pad(table.astype(jnp.float32), ((0, 0), (0, kp - k)))
    ops, arrival, extra = (jnp.pad(x, (0, pad))
                           for x in (ops, arrival, extra))
    n = jnp.reshape(n_ops, (1,)).astype(jnp.int32)

    def tile(k, n):       # blocks past the last op repeat the last block
        return (jnp.minimum(k, jnp.maximum(n[0] - 1, 0) // tt),)

    smem_t = pl.BlockSpec((tt,), tile, memory_space=pltpu.SMEM)

    def whole(size):
        return pl.BlockSpec((size,), lambda k, n: (0,),
                            memory_space=pltpu.SMEM)

    s = n_channels * (MAX_WAYS + 2) + 1
    out_specs = [whole(s)]
    out_shape = [jax.ShapeDtypeStruct((s,), jnp.float32)]
    if completions:
        out_specs.append(smem_t)
        out_shape.append(jax.ShapeDtypeStruct((tp,), jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(tp // tt,),
        in_specs=[whole(7 * kp), smem_t, smem_t, smem_t],
        out_specs=out_specs)
    res = pl.pallas_call(
        functools.partial(_kernel, n_channels=n_channels, kp=kp,
                          batched=batched),
        grid_spec=grid_spec, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret)(n, tab.reshape(-1), ops, arrival, extra)
    if completions:
        return res[0], res[1][:t]
    return res[0], None
