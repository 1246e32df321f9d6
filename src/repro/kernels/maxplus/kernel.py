"""Blocked (max,+) trace-indexed matrix fold — Pallas TPU kernels.

Evaluates ``s_T = A_{idx[T-1]} ⊗ … ⊗ A_{idx[1]} ⊗ A_{idx[0]} ⊗ s_0`` for a
batch of independent design points, where the A_i form a per-op-class
matrix dictionary and ``idx`` is the op-class index sequence of a
heterogeneous trace (``repro.core.maxplus_form.trace_combos`` /
``combo_matrices``).
A homogeneous stream passes ``idx=None`` and falls back to the periodic
gather ``idx[t] = t mod M``.  Layout puts the design-point batch in the
128-wide lane dimension:

    mats: [B, M, N, N]  →  kernel block [M, N, N, BL] (lanes = points)
    s:    [B, N]        →  [N, BL]
    idx:  [T] int32     →  SMEM blocks of TIME_TILE ops

One lane block owns BL=128 design points; the T-step fold runs as a
``fori_loop`` of VPU max/add ops in VMEM, reading ``A[idx[t]]`` each
step by indexing the dictionary ref on its leading axis.  The
trace-indexed paths tile the time axis: per-op scalars (index, arrival,
fault surcharge) arrive in SMEM one ``TIME_TILE`` block at a time and
the state stays resident in the output block across time blocks, so no
operand grows with T.  Blocks must fit ``VMEM_LIMIT_BYTES``; a shape
beyond it raises instead of falling back to interpret mode.

``interpret=None`` (the default everywhere) means "interpret only
off-TPU", decided by :func:`resolve_interpret`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Scoped VMEM the kernels may claim.  The default scoped limit (16 MiB)
#: is smaller than the double-buffered per-lane dictionary of a 2×8
#: mixed trace (~35 MiB); a v5e TensorCore has 128 MiB of VMEM, and the
#: rest is headroom for Mosaic's internal scratch.
VMEM_LIMIT_BYTES = 100 * 2**20

#: Ops per time block of the trace-indexed kernels.  A multiple of 1024:
#: XLA tiles 1-D 32-bit arrays by 1024 elements, and an SMEM block must
#: match that tiling.
TIME_TILE = 1024


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` → interpret exactly when the default backend is not a
    TPU.  The one place that decision is made."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _vmem_bytes(*shapes) -> int:
    """VMEM bytes of double-buffered float32/int32 blocks: the minor two
    dims tile to (8, 128)."""
    total = 0
    for shape in shapes:
        *lead, rows, cols = (1,) * (2 - len(shape)) + tuple(shape)
        total += (math.prod(lead) * -(-rows // 8) * 8 * -(-cols // 128)
                  * 128 * 4)
    return 2 * total


def _check_vmem(what: str, *shapes) -> None:
    need = _vmem_bytes(*shapes)
    if need > VMEM_LIMIT_BYTES:
        raise ValueError(
            f"{what}: blocks {shapes} need {need / 2**20:.1f} MiB of VMEM, "
            f"over the {VMEM_LIMIT_BYTES / 2**20:.0f} MiB limit "
            "(VMEM_LIMIT_BYTES) — use the scan or prefix engine at this "
            "geometry")


def _params(*semantics) -> pltpu.CompilerParams:
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _maxplus_step(mats_ref, i, s):
    a = mats_ref[i]               # [N, N, BL]
    # (max,+) matvec: out[r, b] = max_c (a[r, c, b] + s[c, b])
    return jnp.max(a + s[None, :, :], axis=1)


def _kernel_periodic(mats_ref, s0_ref, *refs, t_steps: int, period: int,
                     with_energy: bool):
    """Homogeneous stream: matrix index is t % period, computed inline.
    With energy, ``refs`` is (e_ref [P, NP, BL], out_ref, acc_ref) and
    the loop also accumulates ``E[t % period]``."""
    if with_energy:
        e_ref, out_ref, acc_ref = refs

        def step(t, c):
            i = t % period
            return _maxplus_step(mats_ref, i, c[0]), c[1] + e_ref[i]

        out_ref[...], acc_ref[...] = jax.lax.fori_loop(
            0, t_steps, step,
            (s0_ref[...], jnp.zeros(acc_ref.shape, acc_ref.dtype)))
    else:
        (out_ref,) = refs
        out_ref[...] = jax.lax.fori_loop(
            0, t_steps, lambda t, s: _maxplus_step(mats_ref, t % period, s),
            s0_ref[...])


def _kernel_indexed(idx_ref, arr_ref, ext_ref, mats_ref, g_ref, w_ref,
                    s0_ref, *refs, t_steps: int, with_energy: bool):
    """Heterogeneous trace, one (lane block, time block) grid step.
    ``idx_ref``/``arr_ref``/``ext_ref`` are this time block's SMEM op
    index, arrival and fault surcharge; ``g_ref``/``w_ref`` [M, N, BL]
    hold the per-combo origin-column templates and written-rows masks.
    Each step is ``s' = max(A_i ⊗ s, g[i] + arrival[t]) + w[i] *
    extra[t]`` — the augmented origin-column contribution of DESIGN.md
    §2.6 plus the written-rows shift of §2.8.  Zero arrivals are the
    identity of the extra max (A_i already bakes the zero-arrival origin
    column); zero extras add +0.0 to every row — exact, NEG included —
    so fault-free traces stay bit-identical.  The state (and with
    energy, the ``E[idx[t]]`` accumulator) lives in the output blocks,
    which stay resident across the time axis."""
    if with_energy:
        e_ref, out_ref, acc_ref = refs
    else:
        (out_ref,) = refs
    k = pl.program_id(1)
    tt = idx_ref.shape[0]

    @pl.when(k == 0)
    def _():
        out_ref[...] = s0_ref[...]
        if with_energy:
            acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    def advance(t, s):
        i = idx_ref[t]
        s = _maxplus_step(mats_ref, i, s)
        s = jnp.maximum(s, g_ref[i] + arr_ref[t])
        return s + w_ref[i] * ext_ref[t]

    n = jnp.minimum(tt, t_steps - k * tt)
    if with_energy:
        out_ref[...], acc_ref[...] = jax.lax.fori_loop(
            0, n, lambda t, c: (advance(t, c[0]), c[1] + e_ref[idx_ref[t]]),
            (out_ref[...], acc_ref[...]))
    else:
        out_ref[...] = jax.lax.fori_loop(0, n, advance, out_ref[...])


def _kernel_fused(nsteps_ref, tab_ref, g_ref, w_ref, idx_ref, arr_ref,
                  ext_ref, s0_ref, out_ref, *, with_arrivals: bool,
                  with_faults: bool):
    """Fused many-trace megakernel, one (lane block, time block) grid
    step: lanes are whole *traces* (one design point), not design points
    of one trace.  Every lane folds its own op-class sequence
    ``idx[:, lane]`` against the one shared matrix dictionary, so a
    fleet of traces is a single ``pallas_call``:

    * per-step per-lane matrix selection is a one-hot ``dot_general``
      of ``tab_ref`` [N·N, M1] (the flattened dictionary, N padded to a
      multiple of 8 so the product reshapes to [N, N, BL] on tile
      boundaries) against ``iota == idx[t]`` — the MXU form, since
      vector-index gathers do not lower on TPU.  It is *exact*: the
      products are 1·x and 0·x = ±0.0 and x + (-0.0) = x;
    * index M (the appended (max,+) identity with a NEG origin template,
      a zero written-rows mask and zero arrival/extra) is the padding
      op: shorter lanes run it past their own length as an exact state
      no-op, so no masking is needed;
    * ``with_faults`` gates the per-op fault-surcharge shift
      ``s += w[idx[t]] * extra[t]`` on the written rows (DESIGN.md
      §2.8); fault-free fleets skip the ops entirely;
    * ``nsteps_ref`` (SMEM scalar prefetch, one entry per lane block)
      bounds the fold at the longest lane *in this block* — lanes sorted
      longest-first mean short-trace blocks exit early instead of
      spinning the global maximum.
    """
    i, k = pl.program_id(0), pl.program_id(1)
    tt, bl = idx_ref.shape
    m1 = tab_ref.shape[1]
    n = out_ref.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, (m1, bl), 0)

    @pl.when(k == 0)
    def _():
        out_ref[...] = s0_ref[...]

    def select(tab, it):
        """[D, M1] table -> [D, BL] per-lane columns (one-hot contraction)."""
        onehot = (iota == it).astype(jnp.float32)
        return jax.lax.dot(tab, onehot, precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)

    def step(t, s):
        it = idx_ref[pl.ds(t, 1), :]                              # [1, BL]
        a = select(tab_ref[...], it).reshape(n, n, bl)
        s2 = jnp.max(a + s[None, :, :], axis=1)
        if with_arrivals:  # all-zero arrivals are dominated by the baked
            # origin column: skip the ops when absent
            s2 = jnp.maximum(s2, select(g_ref[...], it)
                             + arr_ref[pl.ds(t, 1), :])
        if with_faults:
            s2 = s2 + select(w_ref[...], it) * ext_ref[pl.ds(t, 1), :]
        return s2

    steps = jnp.clip(nsteps_ref[i] - k * tt, 0, tt)
    out_ref[...] = jax.lax.fori_loop(0, steps, step, out_ref[...])


def _pad_axis(x, axis: int, size: int, value=0):
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, size - x.shape[axis])
    return jnp.pad(x, pad, constant_values=value)


@functools.partial(jax.jit, static_argnames=("block_lanes", "interpret",
                                             "with_arrivals"))
def maxplus_fold_many_kernel(
    mats: jax.Array,      # [M+1, N, N] shared dictionary, identity at M
    gvec: jax.Array,      # [M+1, N] origin templates, NEG row at M
    idx: jax.Array,       # [B, T] int32 per-lane sequence (M = pad no-op)
    arrivals: jax.Array,  # [B, T] float32 per-lane arrivals (0 = none)
    s0: jax.Array,        # [N] shared initial state
    lengths: jax.Array,   # [B] int32 true op count per lane
    *,
    extras: jax.Array | None = None,  # [B, T] per-lane fault surcharges
    wvec: jax.Array | None = None,    # [M+1, N] written-rows, 0 row at M
    block_lanes: int = 128,
    interpret: bool | None = None,
    with_arrivals: bool = True,
) -> jax.Array:
    """Folded states [B, N] for B independent traces in one launch (see
    ``_kernel_fused``).  Lanes should arrive sorted longest-first so the
    per-block fold bound ``max(lengths[block])`` tracks each block's own
    longest lane.  ``extras`` (with its ``wvec`` written-rows mask)
    carries per-op reliability surcharges; omitted, the fault shift is
    compiled out and fault-free fleets are untouched."""
    from repro.core.maxplus_form import NEG   # the one (max,+) -inf sentinel

    interpret = resolve_interpret(interpret)
    m1, n, _ = mats.shape
    b, t = idx.shape
    with_faults = extras is not None
    if extras is None:
        extras = jnp.zeros((b, t), jnp.float32)
    if wvec is None:
        wvec = jnp.zeros((m1, n), jnp.float32)
    np_ = -(-n // 8) * 8          # state rows padded to the sublane tile
    mp = -(-m1 // 128) * 128      # dictionary padded to the MXU depth
    tt = min(TIME_TILE, -(-t // 8) * 8)
    tp = -(-t // tt) * tt
    bl = min(block_lanes, b)
    bp = -(-b // bl) * bl
    if not interpret:
        _check_vmem("maxplus_fold_many_kernel", (np_ * np_, mp), (np_, mp),
                    (np_, mp), (tt, bl), (tt, bl), (tt, bl), (np_, bl),
                    (np_, bl))
    # padded state rows/cols are NEG (never win a max against real rows);
    # padded dictionary columns are never selected
    mats = _pad_axis(_pad_axis(mats.astype(jnp.float32), 1, np_, NEG),
                     2, np_, NEG)
    tab = _pad_axis(mats.reshape(m1, np_ * np_).T, 1, mp)
    gtab = _pad_axis(_pad_axis(gvec.astype(jnp.float32), 1, np_, NEG).T,
                     1, mp)
    wtab = _pad_axis(_pad_axis(wvec.astype(jnp.float32), 1, np_).T, 1, mp)
    # time pads with the identity op, lanes with identity lanes of length 0
    idx = _pad_axis(_pad_axis(idx.astype(jnp.int32), 1, tp, m1 - 1),
                    0, bp, m1 - 1)
    arrivals = _pad_axis(_pad_axis(arrivals.astype(jnp.float32), 1, tp),
                         0, bp)
    extras = _pad_axis(_pad_axis(extras.astype(jnp.float32), 1, tp), 0, bp)
    lengths = _pad_axis(lengths.astype(jnp.int32), 0, bp)
    nsteps = jnp.max(lengths.reshape(bp // bl, bl), axis=1)
    s0 = _pad_axis(s0.astype(jnp.float32), 0, np_, NEG)

    def whole(block):
        return pl.BlockSpec(block, lambda i, k, ns: (0, 0))

    lanes_t = pl.BlockSpec((tt, bl), lambda i, k, ns: (k, i))
    lanes = pl.BlockSpec((np_, bl), lambda i, k, ns: (0, i))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(bp // bl, tp // tt),
        in_specs=[whole((np_ * np_, mp)), whole((np_, mp)),
                  whole((np_, mp)), lanes_t, lanes_t, lanes_t, lanes],
        out_specs=lanes)
    out = pl.pallas_call(
        functools.partial(_kernel_fused, with_arrivals=with_arrivals,
                          with_faults=with_faults),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((np_, bp), jnp.float32),
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret)(
            nsteps, tab, gtab, wtab, idx.T, arrivals.T, extras.T,
            jnp.broadcast_to(s0[:, None], (np_, bp)))
    return out.T[:b, :n]


@functools.partial(jax.jit, static_argnames=("t_steps", "block_lanes", "interpret"))
def maxplus_fold_kernel(
    mats: jax.Array,     # [B, M, N, N] float32 matrix dictionary
    s0: jax.Array,       # [B, N] float32
    *,
    t_steps: int,
    idx: jax.Array | None = None,   # [t_steps] int32 per-op matrix index
    energy: jax.Array | None = None,  # [B, M, P] per-op phase energies (uJ)
    arrivals: jax.Array | None = None,  # [t_steps] per-op request arrivals
    gvec: jax.Array | None = None,      # [B, M, N] origin-column templates
    extras: jax.Array | None = None,    # [t_steps] per-op fault surcharges
    wvec: jax.Array | None = None,      # [B, M, N] written-rows masks
    block_lanes: int = 128,
    interpret: bool | None = None,
) -> jax.Array | tuple[jax.Array, jax.Array]:
    """Returns the folded state [B, N]; with ``energy`` given, also the
    [B, P] phase-energy accumulator ``sum_t energy[idx[t]]`` computed in
    the same ``fori_loop`` (the per-step matrix index doubles as the
    energy index — DESIGN.md §2.4).

    ``arrivals``/``gvec`` (trace-indexed path only) carry request
    arrival times: each step additionally maxes ``gvec[idx[t]] +
    arrivals[t]`` into the state — the augmented origin-column form of
    DESIGN.md §2.6, keeping the matrix dictionary per-combo instead of
    per-op.  ``extras``/``wvec`` carry per-op reliability surcharges
    shifting each op's written rows after the max-in (DESIGN.md §2.8).
    Omitted, they default to identity values (zero arrivals / NEG
    templates / zero extras / zero masks)."""
    from repro.core.maxplus_form import NEG   # the one (max,+) -inf sentinel

    interpret = resolve_interpret(interpret)
    b, m, n, _ = mats.shape
    if (arrivals is not None or gvec is not None or extras is not None
            or wvec is not None) and idx is None:
        raise ValueError("arrivals/gvec/extras/wvec need the trace-indexed "
                         "path (pass idx)")
    with_energy = energy is not None
    np_ = energy.shape[-1] if with_energy else 0
    bl = min(block_lanes, b)
    bp = -(-b // bl) * bl
    mats_l = jnp.moveaxis(_pad_axis(mats.astype(jnp.float32), 0, bp), 0, -1)
    s0_l = _pad_axis(s0.astype(jnp.float32), 0, bp).T          # [N, Bp]
    blocks = [(m, n, n, bl), (n, bl), (n, bl)]
    if with_energy:
        blocks += [(m, np_, bl), (np_, bl)]
        e_l = jnp.moveaxis(_pad_axis(energy.astype(jnp.float32), 0, bp),
                           0, -1)                              # [M, P, Bp]
    out_shape = [jax.ShapeDtypeStruct((n, bp), jnp.float32)]
    if with_energy:
        out_shape.append(jax.ShapeDtypeStruct((np_, bp), jnp.float32))

    if idx is None:                      # periodic: no per-op operands
        if not interpret:
            _check_vmem("maxplus_fold_kernel (periodic)", *blocks)

        def spec(block):
            return pl.BlockSpec(block,
                                lambda i: (0,) * (len(block) - 1) + (i,))

        in_specs = [spec((m, n, n, bl)), spec((n, bl))]
        operands = [mats_l, s0_l]
        if with_energy:
            in_specs.append(spec((m, np_, bl)))
            operands.append(e_l)
        call = pl.pallas_call(
            functools.partial(_kernel_periodic, t_steps=t_steps, period=m,
                              with_energy=with_energy),
            grid=(bp // bl,), in_specs=in_specs,
            out_specs=[spec(s.shape[:-1] + (bl,)) for s in out_shape],
            out_shape=out_shape, compiler_params=_params("parallel"),
            interpret=interpret)
    else:                                # trace-indexed: time-tiled
        tt = min(TIME_TILE, -(-t_steps // 8) * 8)
        tp = -(-t_steps // tt) * tt
        blocks += [(m, n, bl), (m, n, bl)]
        if not interpret:
            _check_vmem("maxplus_fold_kernel (trace-indexed)", *blocks)
        g_l = (jnp.full((m, n, bp), NEG, jnp.float32) if gvec is None
               else jnp.moveaxis(_pad_axis(gvec.astype(jnp.float32), 0, bp),
                                 0, -1))                       # [M, N, Bp]
        w_l = (jnp.zeros((m, n, bp), jnp.float32) if wvec is None
               else jnp.moveaxis(_pad_axis(wvec.astype(jnp.float32), 0, bp),
                                 0, -1))                       # [M, N, Bp]

        def per_op(x, dtype):            # [t_steps] -> [tp], zero padded
            x = jnp.zeros((t_steps,), dtype) if x is None else x
            return _pad_axis(x.astype(dtype)[:t_steps], 0, tp)

        def spec(block):
            return pl.BlockSpec(
                block, lambda i, k: (0,) * (len(block) - 1) + (i,))

        smem = pl.BlockSpec((tt,), lambda i, k: (k,),
                            memory_space=pltpu.SMEM)
        in_specs = [smem, smem, smem, spec((m, n, n, bl)),
                    spec((m, n, bl)), spec((m, n, bl)), spec((n, bl))]
        operands = [per_op(idx, jnp.int32), per_op(arrivals, jnp.float32),
                    per_op(extras, jnp.float32), mats_l, g_l, w_l, s0_l]
        if with_energy:
            in_specs.append(spec((m, np_, bl)))
            operands.append(e_l)
        call = pl.pallas_call(
            functools.partial(_kernel_indexed, t_steps=t_steps,
                              with_energy=with_energy),
            grid=(bp // bl, tp // tt), in_specs=in_specs,
            out_specs=[spec(s.shape[:-1] + (bl,)) for s in out_shape],
            out_shape=out_shape,
            compiler_params=_params("parallel", "arbitrary"),
            interpret=interpret)
    res = call(*operands)
    if not with_energy:
        return res[0].T[:b]                  # [B, N]
    out, acc = res
    return out.T[:b], acc.T[:b]              # [B, N], [B, P]
