"""Public ops: SSD completion times via the (max,+) Pallas kernel.

Two entry points mirror the two scan-engine paths in ``repro.core``:

* ``channel_end_time_maxplus`` — homogeneous single-channel design-point
  batches (periodic matrix form; ways must divide MAX_WAYS — the
  power-of-two sweep grid of the paper);
* ``trace_end_time_maxplus`` — one heterogeneous ``OpTrace`` evaluated
  for a batch of design-point ``OpClassTable``s (the matrix-dictionary
  form; DESIGN.md §2.1).

Both take a ``strategy`` (DESIGN.md §2.3):

* ``"sequential"`` — the O(T) Pallas ``fori_loop`` matvec fold
  (``repro.kernels.maxplus.kernel``; ``interpret=None`` compiles it on a
  TPU and interprets it elsewhere);
* ``"segmented"`` — the segmented parallel-prefix matmul fold,
  O(segment_len + log T) depth;
* ``"squaring"`` (homogeneous only) — periodic matrix squaring,
  O(log n_pages) matmuls.

``trace_energy_maxplus`` additionally accumulates the phase-resolved
per-op energies ``E[idx[t]]`` inside the kernel's fold (DESIGN.md §2.4).

Engine-level dispatch lives in ``repro.core.api``: this module is the
``"pallas"`` entry of the registry, and ``strategy`` remains a
kernel-local knob selecting the fold shape.  Policy strings are
validated by ``repro.core.sim.policy_is_batched`` on the matrix-build
path, so typos raise instead of silently simulating ``eager``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.maxplus_form import (NEG, StateLayout, combo_arrival_offsets,
                                     combo_matrices, combo_written_rows,
                                     end_time_from_state, init_state,
                                     maxplus_eye, maxplus_fold_segmented,
                                     periodic_fold_squaring, trace_combos,
                                     transition_matrices)
from repro.core.sim import PageOpParams
from repro.kernels.maxplus.kernel import (maxplus_fold_kernel,
                                          maxplus_fold_many_kernel)
from repro.kernels.maxplus.ref import maxplus_fold_ref


def _augment_arrivals(mats, gvec, idx, arrivals, wvec=None, extras=None):
    """[B, T, N, N] per-op matrices with the arrival origin column maxed
    in and the fault surcharge added to the written rows — the dense
    expansion the segmented strategy folds when a trace carries arrivals
    or per-op extras (the sequential kernel keeps the compact per-combo
    dictionary and applies ``gvec[idx[t]] + arrivals[t]`` /
    ``wvec[idx[t]] * extras[t]`` per step instead).  The origin row is
    the last layout row by construction.  Adding ``extras[t]`` uniformly
    across a written row commutes bit-exactly with the row max (rounding
    is monotone), so the dense form reproduces the per-step one."""
    per = jnp.take(mats, idx, axis=1)                       # [B, T, N, N]
    if arrivals is not None:
        cand = jnp.take(gvec, idx, axis=1) + arrivals[None, :, None]
        per = per.at[..., -1].set(jnp.maximum(per[..., -1], cand))
    if extras is not None:
        shift = jnp.take(wvec, idx, axis=1) * extras[None, :, None]
        per = per + shift[..., None]                        # all columns
    return per


def maxplus_fold(mats, s0, *, t_steps: int, idx=None, use_kernel: bool = True,
                 interpret: bool | None = None, strategy: str = "sequential",
                 segment_len: int = 64, arrivals=None, gvec=None,
                 extras=None, wvec=None):
    """Fold dispatch: ``strategy`` picks the evaluation shape (see module
    docstring); ``use_kernel=False`` runs the jnp sequential reference.
    ``arrivals`` [T] + ``gvec`` [B, M, N] make the fold arrival-aware;
    ``extras`` [T] + ``wvec`` [B, M, N] add per-op reliability
    surcharges on the written rows (trace-indexed path only; DESIGN.md
    §2.6 / §2.8)."""
    if (arrivals is not None or extras is not None) and idx is None:
        raise ValueError("arrivals/extras need the trace-indexed path "
                         "(pass idx)")
    if strategy == "segmented":
        if idx is None:
            idx = jnp.arange(t_steps, dtype=jnp.int32) % mats.shape[-3]
        idx = idx[:t_steps]
        if arrivals is not None or extras is not None:
            mats = _augment_arrivals(
                mats, gvec, idx,
                None if arrivals is None else jnp.asarray(arrivals,
                                                          jnp.float32),
                wvec,
                None if extras is None else jnp.asarray(extras,
                                                        jnp.float32))
            idx = jnp.arange(t_steps, dtype=jnp.int32)
        return maxplus_fold_segmented(mats, idx, s0,
                                      segment_len=segment_len)
    if strategy == "squaring":
        if idx is not None:
            raise ValueError(
                "strategy='squaring' needs a periodic (homogeneous) "
                "stream — got an explicit idx sequence")
        return periodic_fold_squaring(mats, s0, t_steps)
    if strategy != "sequential":
        raise ValueError(f"unknown strategy {strategy!r} (one of "
                         "'sequential', 'segmented', 'squaring')")
    if use_kernel:
        return maxplus_fold_kernel(mats, s0, t_steps=t_steps, idx=idx,
                                   arrivals=arrivals, gvec=gvec,
                                   extras=extras, wvec=wvec,
                                   interpret=interpret)
    return maxplus_fold_ref(mats, s0, t_steps=t_steps, idx=idx,
                            arrivals=arrivals, gvec=gvec,
                            extras=extras, wvec=wvec)


def channel_end_time_maxplus(
    ops: list[PageOpParams],
    ways: list[int],
    *,
    n_pages: int,
    policy: str = "eager",
    use_kernel: bool = True,
    interpret: bool | None = None,
    strategy: str = "sequential",
) -> jax.Array:
    """Completion times (us) for a batch of homogeneous design points."""
    mats = np.stack([transition_matrices(op, w, policy)
                     for op, w in zip(ops, ways)])
    s0 = np.broadcast_to(init_state(), (mats.shape[0],
                                        init_state().shape[0])).copy()
    final = maxplus_fold(jnp.asarray(mats), jnp.asarray(s0),
                         t_steps=n_pages, use_kernel=use_kernel,
                         interpret=interpret, strategy=strategy)
    return end_time_from_state(np.asarray(final))


def bandwidth_maxplus_mb_s(ops, ways, *, n_pages: int = 512,
                           policy: str = "eager", **kw) -> np.ndarray:
    end = channel_end_time_maxplus(ops, ways, n_pages=n_pages, policy=policy, **kw)
    data = np.array([op.data_bytes for op in ops], np.float64)
    return data * n_pages / np.asarray(end)


def _combo_setup(tables, trace, policy):
    """(layout, combos, idx, mats [B,M,N,N], s0 [B,N], arrivals, gvec,
    extras, wvec) shared by the trace-indexed end-time and energy entry
    points.  ``arrivals``/``gvec`` are None for back-to-back traces; an
    arrival-aware trace additionally gets the per-combo origin-column
    templates of ``combo_arrival_offsets`` (DESIGN.md §2.6).
    ``extras``/``wvec`` (None for fault-free traces) carry the per-op
    reliability surcharges and the per-combo written-rows masks they
    shift (DESIGN.md §2.8)."""
    layout = StateLayout(trace.channels, trace.ways)
    combos, idx = trace_combos(trace)   # trace-only: shared by the batch
    mats = np.stack([combo_matrices(table, combos, layout, policy)
                     for table in tables])
    s0 = np.broadcast_to(init_state(layout),
                         (mats.shape[0], layout.n_state)).copy()
    arrivals = gvec = None
    if trace.arrival_us is not None:
        arrivals = jnp.asarray(trace.arrival_us, jnp.float32)
        gvec = jnp.asarray(np.stack([
            combo_arrival_offsets(table, combos, layout, policy)
            for table in tables]))
    extras = wvec = None
    if trace.extra_us is not None:
        extras = jnp.asarray(trace.extra_us, jnp.float32)
        w = combo_written_rows(combos, layout)          # combo-only: shared
        wvec = jnp.asarray(np.broadcast_to(w, (mats.shape[0],) + w.shape))
    return layout, combos, idx, mats, s0, arrivals, gvec, extras, wvec


def trace_end_time_maxplus(
    tables,                    # OpClassTable | list[OpClassTable]
    trace,                     # OpTrace (shared across the batch)
    *,
    policy: str = "eager",
    use_kernel: bool = True,
    interpret: bool | None = None,
    strategy: str = "sequential",
    segment_len: int = 64,
) -> np.ndarray:
    """Completion times (us) of one heterogeneous trace under a batch of
    design-point timing tables ([B], or scalar for a single table)."""
    single = not isinstance(tables, (list, tuple))
    if single:
        tables = [tables]
    layout, _, idx, mats, s0, arrivals, gvec, extras, wvec = _combo_setup(
        tables, trace, policy)
    final = maxplus_fold(jnp.asarray(mats), jnp.asarray(s0),
                         t_steps=trace.n_ops, idx=jnp.asarray(idx),
                         use_kernel=use_kernel, interpret=interpret,
                         strategy=strategy, segment_len=segment_len,
                         arrivals=arrivals, gvec=gvec,
                         extras=extras, wvec=wvec)
    end = end_time_from_state(np.asarray(final), layout)
    return end[0] if single else end


def trace_fold_closure(table, trace, *, policy: str = "eager"):
    """(fn, args): the jax-traceable core of the trace-indexed kernel
    path — what the ``repro.analysis`` jaxpr layer traces for the
    ``pallas`` engine (DESIGN.md §2.9).  The host-side combo-dictionary
    build happens here, *outside* the returned closure, exactly as in
    :func:`trace_end_time_maxplus`; the closure itself is the pure
    ``maxplus_fold`` the registry entry folds per query (interpret
    mode, so the pallas_call traces off-TPU)."""
    _, _, idx, mats, s0, arrivals, gvec, extras, wvec = _combo_setup(
        [table], trace, policy)
    fold = functools.partial(
        maxplus_fold, t_steps=trace.n_ops, interpret=True,
        strategy="sequential", arrivals=arrivals, gvec=gvec,
        extras=extras, wvec=wvec)

    def fn(mats, s0, idx):
        return fold(mats, s0, idx=idx)

    return fn, (jnp.asarray(mats), jnp.asarray(s0), jnp.asarray(idx))


def run_many_end_time_maxplus(
    table,                     # OpClassTable (one design point)
    traces,                    # list[OpTrace], one shared (C, W) geometry
    *,
    policy: str = "eager",
    block_lanes: int = 128,
    interpret: bool | None = None,
) -> np.ndarray:
    """End times (us) of B independent heterogeneous traces in ONE fused
    Pallas launch (``maxplus_fold_many_kernel``): lanes are whole traces
    rather than design points, folding their own op sequences against the
    *union* combo dictionary of the fleet.  An appended (max,+) identity
    combo (NEG origin template, zero arrival) pads short lanes as an
    exact no-op, so mixed-length fleets need no per-bucket launches —
    lanes sort longest-first and each lane block folds only to its own
    longest member.  Lane count and fold length round up to the next
    block / power-of-two so jittered fleet sizes reuse the compiled
    program."""
    if not traces:
        return np.zeros((0,), np.float64)
    geom = (traces[0].channels, traces[0].ways)
    for tr in traces:
        if (tr.channels, tr.ways) != geom:
            raise ValueError(
                "fused run_many needs one shared (channels, ways) geometry "
                f"per call — got {geom} and {(tr.channels, tr.ways)}")
    layout = StateLayout(*geom)
    # union combo dictionary across the fleet, vectorised: pack each
    # op's (class, channel, way, parity) into one integer key and let
    # np.unique build the dictionary + per-op indices in one pass — the
    # per-trace Python loop of ``trace_combos`` would dominate the
    # megakernel's own wall time at fleet scale
    keys = np.concatenate([
        (np.asarray(tr.cls, np.int64) << 24)
        | (np.asarray(tr.channel, np.int64) << 16)
        | (np.asarray(tr.way, np.int64) << 8)
        | (np.asarray(tr.parity, np.int64) & 1)
        for tr in traces])
    uniq, inv = np.unique(keys, return_inverse=True)
    combos = [(int(k >> 24), int((k >> 16) & 0xFF),
               int((k >> 8) & 0xFF), int(k & 1)) for k in uniq]
    bounds = np.cumsum([0] + [tr.n_ops for tr in traces])
    lane_idx = [inv[bounds[i]:bounds[i + 1]].astype(np.int32)
                for i in range(len(traces))]
    m = len(combos)
    mats = np.concatenate([combo_matrices(table, combos, layout, policy),
                           maxplus_eye(layout.n_state)[None]])
    gvec = np.concatenate([combo_arrival_offsets(table, combos, layout,
                                                 policy),
                           np.full((1, layout.n_state), NEG, np.float32)])
    order = sorted(range(len(traces)), key=lambda i: -traces[i].n_ops)
    t_max = 1 << max(6, (traces[order[0]].n_ops - 1).bit_length())
    b = len(traces)
    idx = np.full((b, t_max), m, np.int32)
    arr = np.zeros((b, t_max), np.float32)
    ext = np.zeros((b, t_max), np.float32)
    lengths = np.zeros((b,), np.int32)
    for lane, i in enumerate(order):
        tr = traces[i]
        idx[lane, :tr.n_ops] = lane_idx[i]
        if tr.arrival_us is not None:
            arr[lane, :tr.n_ops] = np.asarray(tr.arrival_us, np.float32)
        if tr.extra_us is not None:
            ext[lane, :tr.n_ops] = np.asarray(tr.extra_us, np.float32)
        lengths[lane] = tr.n_ops
    # fault surcharges ride a written-rows mask over the union dictionary
    # (zero row for the padding identity); all-zero fleets compile the
    # shift out so fault-free runs stay bit-identical
    with_faults = bool(ext.any())
    if with_faults:
        wvec = np.concatenate([combo_written_rows(combos, layout),
                               np.zeros((1, layout.n_state), np.float32)])
        extras_arg, wvec_arg = jnp.asarray(ext), jnp.asarray(wvec)
    else:
        extras_arg = wvec_arg = None
    final = maxplus_fold_many_kernel(
        jnp.asarray(mats), jnp.asarray(gvec), jnp.asarray(idx),
        jnp.asarray(arr), jnp.asarray(init_state(layout)),
        jnp.asarray(lengths), extras=extras_arg, wvec=wvec_arg,
        block_lanes=block_lanes, interpret=interpret,
        with_arrivals=bool(arr.any()))
    end = end_time_from_state(np.asarray(final), layout)
    out = np.empty((b,), np.float64)
    out[np.asarray(order)] = end
    return out


def combo_energy_uj(table, combos, kind) -> np.ndarray:
    """[M, P] phase-energy vector per (class, channel, way, parity) combo
    — the energy twin of ``combo_matrices`` (parity resolved here, so the
    kernel's per-step gather index serves both)."""
    from repro.core.energy import op_phase_energy_uj

    e = op_phase_energy_uj(table, kind)            # [K, 2, P]
    return np.stack([e[k, par] for k, _c, _w, par in combos])


def trace_energy_maxplus(
    tables,                    # OpClassTable | list[OpClassTable]
    trace,                     # OpTrace (shared across the batch)
    kinds,                     # InterfaceKind | list[InterfaceKind]
    *,
    policy: str = "eager",
    use_kernel: bool = True,
    interpret: bool | None = None,
    strategy: str = "sequential",
    segment_len: int = 64,
) -> tuple[np.ndarray, np.ndarray]:
    """(end_us, phase-energy sums in uJ) of one trace under a batch of
    design points ([B] / [B, P], or scalar / [P] for a single table).

    ``strategy="sequential"`` accumulates ``E[idx[t]]`` inside the Pallas
    ``fori_loop`` next to the (max,+) matvec (DESIGN.md §2.4); the
    segmented strategy folds the end time as usual and reduces the
    energy as the plain segment sum it is."""
    single = not isinstance(tables, (list, tuple))
    if single:
        tables, kinds = [tables], [kinds]
    if len(kinds) != len(tables):
        raise ValueError("need one interface kind per op-class table")
    layout, combos, idx, mats, s0, arrivals, gvec, extras, wvec = \
        _combo_setup(tables, trace, policy)
    e = np.stack([combo_energy_uj(table, combos, kind)
                  for table, kind in zip(tables, kinds)])
    if strategy == "sequential":
        if use_kernel:
            final, acc = maxplus_fold_kernel(
                jnp.asarray(mats), jnp.asarray(s0), t_steps=trace.n_ops,
                idx=jnp.asarray(idx), energy=jnp.asarray(e),
                arrivals=arrivals, gvec=gvec, extras=extras, wvec=wvec,
                interpret=interpret)
        else:
            final = maxplus_fold_ref(jnp.asarray(mats), jnp.asarray(s0),
                                     t_steps=trace.n_ops,
                                     idx=jnp.asarray(idx),
                                     arrivals=arrivals, gvec=gvec,
                                     extras=extras, wvec=wvec)
            acc = jnp.sum(jnp.asarray(e)[:, idx, :], axis=1)
    elif strategy == "segmented":
        final = maxplus_fold(
            jnp.asarray(mats), jnp.asarray(s0), t_steps=trace.n_ops,
            idx=jnp.asarray(idx), strategy="segmented",
            segment_len=segment_len, arrivals=arrivals, gvec=gvec,
            extras=extras, wvec=wvec)
        acc = jnp.sum(jnp.asarray(e)[:, idx, :], axis=1)
    else:
        raise ValueError(f"unknown trace energy strategy {strategy!r} "
                         "(one of 'sequential', 'segmented')")
    end = end_time_from_state(np.asarray(final), layout)
    acc = np.asarray(acc)
    return (end[0], acc[0]) if single else (end, acc)


def trace_bandwidth_maxplus_mb_s(tables, trace, **kw) -> np.ndarray:
    """Aggregate payload bandwidth (MB/s) of a trace per design point."""
    single = not isinstance(tables, (list, tuple))
    end = trace_end_time_maxplus(tables, trace, **kw)
    if single:
        return trace.total_bytes(tables) / end
    data = np.array([trace.total_bytes(t) for t in tables], np.float64)
    return data / np.asarray(end)
