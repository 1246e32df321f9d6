"""Compiled FTL translation: the host translator of ``repro.core.ftl``
re-expressed as a ``jax.lax.scan`` state machine (DESIGN.md §2.11).

``ftl.translate`` walks the host stream with a per-op Python loop over
dicts and deques — correct, but the one stage of the pipeline that is
neither jittable nor batchable, so aged design-space sweeps pay serial
Python per point and stop at the FTL boundary.  This module compiles
the *same* translation:

* the L2P/P2L maps, per-block valid counts, fill sequence, erase
  counts and the free-block FIFO (a ring buffer with monotonic
  head/tail cursors) are dense ``int32`` arrays carried through one
  ``lax.scan``;
* the scan runs in **fused steps**.  A one-op-per-step machine is the
  natural shape, but its wall time is linear in the *physical* op
  count — GC relocations included — which on an aged drive is several
  times the host stream.  Instead each step is either a **host
  burst** (up to ``pages_per_block`` host ops, cut before the first
  op that would need a block allocation or fire the GC trigger — both
  are prefix-closed conditions, so the burst length is one masked
  ``cumsum``), a **single allocating write** (the old scalar path,
  taken when the burst would be empty), or a **whole GC cycle**
  (every valid page of the victim relocated by one vectorised
  scatter pass — at most one block opens per cycle since a victim
  holds at most ``pages_per_block`` valid pages — then the erase,
  the guard and the trigger re-check).  Step count is then the burst
  count plus the GC cycle count, ~an order less than the op count;
* each step emits into one row of a bounded ``[t_max, 2*ppb + 1]``
  output buffer: burst ops in lanes ``0..ppb-1``, a GC cycle's
  read/write pairs at ``(2i, 2i+1)`` with the erase at lane ``2k`` —
  disjoint by construction, padding lanes payload-masked (the §2.5
  masked-fold identity), so flattening rows in order recovers the
  exact host op sequence and the whole translate→lower→simulate
  chain is one jittable closure;
* victim selection is a cascaded masked argmin reproducing the host's
  ``np.lexsort`` tie-break exactly: greedy = (valid count, fill seq,
  block id), lru = (fill seq, block id);
* the host translator survives as the **oracle**: the scan path agrees
  with it op-for-op — same op classes, arrivals, payload flags,
  request ids and stats — on every fault-free translation, and its
  jaxpr joins the §2.9 invariant gates (RNG-free, f32 floats,
  primitive budget).

Read-modify-write (sector-addressed streams, ``ftl`` module
docstring) is decided before the machine runs: a logical page, once
mapped, stays mapped (GC relocates it, nothing unmaps it), so a partial
write reads the old copy exactly when its page was mapped in the
machine's input state or written earlier in the stream
(``rmw_mask``).  Each such read goes into the host stream as a
payload-free read right before its write, so the machine — and its
compiled program — is the page-granular one, and its burst emits the
read in the lane before the write, as the host oracle orders them.

Block-level fault injection (``prog_fail_prob`` / ``erase_fail_prob``)
stays on the host path: its per-attempt RNG draws would put RNG
primitives inside the fold, which the determinism contract forbids —
``repro.core.api`` falls back to ``ftl.translate`` whenever those
probabilities are nonzero.

Error handling is deferred: the machine latches an error *bit* and
freezes (all later steps are state no-ops), and ``translate_scan``
raises the matching host ``RuntimeError`` after the fold returns.  An
output buffer that proves too short is not an error — the caller
doubles ``t_max`` and re-runs from the same (functional) input state.
"""

from __future__ import annotations

import functools
import math
import typing

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spans as _spans
from repro.core.ftl import (ERASE, FTL_READ, FTL_WRITE, FTLSpec, FTLState,
                            FTLStats, FTLTranslation, GC_READ, GC_WRITE,
                            analytic_waf, host_write_bytes,
                            precondition_lpns)
from repro.core.trace import READ, WRITE
from repro.core.workload import (RequestStream, request_lpns, request_ops,
                                 request_partial)

#: ``mode`` register values (HOST bursts host ops; GC drains one whole
#: relocation cycle per step until the trigger clears).
MODE_HOST, MODE_GC = 0, 1

#: Latched error bits (decoded to the host translator's RuntimeErrors).
ERR_NO_FREE, ERR_GUARD, ERR_NO_CAND, ERR_ALL_VALID = 1, 2, 4, 8

_BIG = 2 ** 30


class ScanFTLState(typing.NamedTuple):
    """The dense-array drive state one translation scan carries.  All
    integers are ``int32`` (the x64-retrace gate keeps them that way),
    floats are ``float32``.  ``l2p`` is padded to ``total_pages`` so
    overprovisioning sweeps at fixed geometry share one compiled fold
    (entries past ``logical_pages`` stay -1 forever)."""

    l2p: jax.Array          # int32 [total_pages] lpn -> ppn, -1 unmapped
    p2l: jax.Array          # int32 [total_pages] ppn -> lpn, -1 invalid
    valid_count: jax.Array  # int32 [blocks]
    full: jax.Array         # bool  [blocks]
    fill_seq: jax.Array     # int32 [blocks] open order, -1 = not filled
    erase_count: jax.Array  # int32 [blocks] lifetime erases (wear)
    free_q: jax.Array       # int32 [blocks] FIFO ring of free block ids
    free_head: jax.Array    # int32 [] monotonic pop cursor
    free_tail: jax.Array    # int32 [] monotonic push cursor
    open_block: jax.Array   # int32 []
    next_page: jax.Array    # int32 [] frontier offset in the open block
    seq: jax.Array          # int32 [] next fill_seq value
    h: jax.Array            # int32 [] host ops consumed *this fold*
    mode: jax.Array         # int32 [] MODE_*
    victim: jax.Array       # int32 [] current GC victim block
    guard: jax.Array        # int32 [] GC cycles since the last host write
    arrival: jax.Array      # f32   [] triggering host arrival (GC inherits)
    watermark: jax.Array    # int32 [] free-page low watermark
    host_w: jax.Array       # int32 [] stats: host pages written
    total_w: jax.Array      # int32 [] stats: physical pages written
    gc_pages: jax.Array     # int32 [] stats: pages relocated
    gc_reads: jax.Array     # int32 [] stats: GC reads emitted
    gc_writes: jax.Array    # int32 [] stats: GC writes emitted
    erases: jax.Array       # int32 [] stats: erases emitted
    err: jax.Array          # int32 [] latched ERR_* bits (0 = healthy)


def _i32(x) -> jax.Array:
    return jnp.asarray(x, jnp.int32)


def scan_state_fresh(spec: FTLSpec) -> ScanFTLState:
    """A fresh drive in scan form — field-for-field the state
    ``ftl.FTLState(spec)`` starts from (block 0 open, blocks 1.. free)."""
    blocks, total = spec.blocks, spec.total_pages
    free_q = np.zeros(blocks, np.int32)
    free_q[: blocks - 1] = np.arange(1, blocks, dtype=np.int32)
    fill_seq = np.full(blocks, -1, np.int32)
    fill_seq[0] = 0
    z = _i32(0)
    return ScanFTLState(
        l2p=jnp.full((total,), -1, jnp.int32),
        p2l=jnp.full((total,), -1, jnp.int32),
        valid_count=jnp.zeros((blocks,), jnp.int32),
        full=jnp.zeros((blocks,), bool),
        fill_seq=jnp.asarray(fill_seq),
        erase_count=jnp.zeros((blocks,), jnp.int32),
        free_q=jnp.asarray(free_q), free_head=z, free_tail=_i32(blocks - 1),
        open_block=z, next_page=z, seq=_i32(1), h=z, mode=z, victim=z,
        guard=z, arrival=jnp.float32(0.0),
        watermark=_i32(total), host_w=z, total_w=z, gc_pages=z,
        gc_reads=z, gc_writes=z, erases=z, err=z)


def scan_state_from_host(state: FTLState) -> ScanFTLState:
    """Convert a host ``FTLState`` (chained aging) into scan form.
    Rejects states carrying block-level fault history — the scan path
    is the fault-free translation engine."""
    if state.bad.any() or state.retired.any():
        raise ValueError(
            "scan translation requires a fault-free drive state "
            "(bad/retired blocks present — use ftl.translate)")
    spec = state.spec
    blocks, total = spec.blocks, spec.total_pages
    l2p = np.full(total, -1, np.int32)
    l2p[: spec.logical_pages] = state.l2p
    free = np.fromiter(state.free, np.int32, len(state.free))
    free_q = np.zeros(blocks, np.int32)
    free_q[: len(free)] = free
    st = state.stats
    return ScanFTLState(
        l2p=jnp.asarray(l2p), p2l=jnp.asarray(state.p2l, jnp.int32),
        valid_count=jnp.asarray(state.valid_count, jnp.int32),
        full=jnp.asarray(state.full, bool),
        fill_seq=jnp.asarray(state.fill_seq, jnp.int32),
        erase_count=jnp.asarray(state.erase_count, jnp.int32),
        free_q=jnp.asarray(free_q), free_head=_i32(0),
        free_tail=_i32(len(free)), open_block=_i32(state.open_block),
        next_page=_i32(state.next_page), seq=_i32(state._seq),
        h=_i32(0), mode=_i32(0), victim=_i32(0),
        guard=_i32(0), arrival=jnp.float32(0.0),
        watermark=_i32(st.free_page_low_watermark),
        host_w=_i32(st.host_pages_written),
        total_w=_i32(st.total_pages_written),
        gc_pages=_i32(st.gc_pages_moved), gc_reads=_i32(st.gc_reads),
        gc_writes=_i32(st.gc_writes), erases=_i32(st.erases), err=_i32(0))


def scan_state_to_host(fs: ScanFTLState, spec: FTLSpec) -> FTLState:
    """Materialise a scan state back into the host ``FTLState`` form, so
    chained aging studies and the existing result plumbing are agnostic
    to which translator ran."""
    st = FTLState(spec)
    st.l2p = np.asarray(fs.l2p, np.int64)[: spec.logical_pages].copy()
    st.p2l = np.asarray(fs.p2l, np.int64).copy()
    st.valid_count = np.asarray(fs.valid_count, np.int64).copy()
    st.full = np.asarray(fs.full, bool).copy()
    st.fill_seq = np.asarray(fs.fill_seq, np.int64).copy()
    st.erase_count = np.asarray(fs.erase_count, np.int64).copy()
    head, tail = int(fs.free_head), int(fs.free_tail)
    q = np.asarray(fs.free_q)
    idx = (head + np.arange(tail - head)) % spec.blocks
    st.free.clear()
    st.free.extend(int(b) for b in q[idx])
    st.open_block = int(fs.open_block)
    st.next_page = int(fs.next_page)
    st._seq = int(fs.seq)
    st.stats = _stats_from(fs)
    return st


def _stats_from(fs: ScanFTLState) -> FTLStats:
    ec = np.asarray(fs.erase_count)
    return FTLStats(
        host_pages_written=int(fs.host_w),
        total_pages_written=int(fs.total_w),
        gc_pages_moved=int(fs.gc_pages), gc_reads=int(fs.gc_reads),
        gc_writes=int(fs.gc_writes), erases=int(fs.erases),
        free_page_low_watermark=int(fs.watermark),
        max_erase_count=int(ec.max()), mean_erase_count=float(ec.mean()))


def make_translate_fold(blocks: int, ppb: int, n_host: int, t_max: int,
                        unroll: int = 1):
    """Build the translation scan for a static ``(blocks, ppb, n_host,
    t_max)`` shape.  The returned function is pure and traceable (the
    §2.9 gates trace it directly)::

        fold(cls_h, arr_h, pay_h, rid_h, lpn_h, n_eff, gc_free, is_lru,
             state) -> (state', (op_cls, arrival, payload, rid, valid))

    Host arrays are ``[n_host]`` (padded; ``n_eff`` ops are real, and
    ``n_host >= n_eff + ppb`` so the per-step host window never
    clamps).  The emitted arrays are ``[t_max, 2*ppb + 1]`` rows —
    one fused step each; flattening row-major and keeping ``valid``
    lanes recovers the host op order (GC membership needs no lane of
    its own: it is exactly ``op_cls >= GC_READ``).  ``gc_free`` /
    ``is_lru`` are traced scalars so GC-trigger and policy sweeps at
    fixed geometry share one compile; steps past the stream idle
    (every lane payload-masked), so an incomplete run is detected from
    ``(h, mode)`` and re-run with a doubled buffer."""
    total = blocks * ppb
    S = 2 * ppb + 1
    lanes = jnp.arange(ppb, dtype=jnp.int32)
    not_eye = ~jnp.eye(ppb, dtype=bool)
    barange = jnp.arange(blocks, dtype=jnp.int32)
    jlanes = jnp.arange(S, dtype=jnp.int32)
    gc_pat = jnp.where(jlanes % 2 == 0, _i32(GC_READ), _i32(GC_WRITE))

    def fold(cls_h, arr_h, pay_h, rid_h, lpn_h, n_eff, gc_free, is_lru,
             state):
        cls_h = jnp.asarray(cls_h, jnp.int32)
        arr_h = jnp.asarray(arr_h, jnp.float32)
        pay_h = jnp.asarray(pay_h, bool)
        rid_h = jnp.asarray(rid_h, jnp.int32)
        lpn_h = jnp.asarray(lpn_h, jnp.int32)
        n_eff = _i32(n_eff)
        gc_free = _i32(gc_free)
        is_lru = jnp.asarray(is_lru, bool)

        def step(s, _):
            # One branchless fused step: both paths (host burst / GC
            # cycle) run every step as predicated vector math — a
            # vmapped `lax.switch` would run all branches anyway, so a
            # single shared code path costs the same batched or not,
            # and every scatter below self-gates with a drop index.
            active = (s.err == 0) & ~((s.mode == MODE_HOST)
                                      & (s.h >= n_eff))
            in_host = active & (s.mode == MODE_HOST)
            in_gc = active & (s.mode == MODE_GC)

            # -- host burst: the next ppb-op window, cut at the first
            # op needing a block allocation (cumulative writes exceed
            # the open block's room) or — when the free pool already
            # sits at the trigger — at the first write, whose landing
            # must re-check GC.  Both cuts are prefix-closed, so the
            # burst length is the popcount of one mask.
            hc = jnp.clip(s.h, 0, n_host - ppb)
            wcls = jax.lax.dynamic_slice(cls_h, (hc,), (ppb,))
            warr = jax.lax.dynamic_slice(arr_h, (hc,), (ppb,))
            wpay = jax.lax.dynamic_slice(pay_h, (hc,), (ppb,))
            wrid = jax.lax.dynamic_slice(rid_h, (hc,), (ppb,))
            wlpn = jax.lax.dynamic_slice(lpn_h, (hc,), (ppb,))
            stream_ok = in_host & (hc + lanes < n_eff)
            w_lane = stream_ok & (wcls == WRITE)
            room = ppb - s.next_page
            w_cum = jnp.cumsum(w_lane.astype(jnp.int32))
            fits = stream_ok & (w_cum <= room)
            low = (s.free_tail - s.free_head) <= gc_free
            any_w = jnp.any(w_lane)
            fw = jnp.argmax(w_lane).astype(jnp.int32)
            allow = fits & (~low | ~any_w | (lanes <= fw))
            K = jnp.sum(allow, dtype=jnp.int32)
            b_open = in_host & (K == 0)      # head write needs a block
            take = in_host & (lanes < jnp.where(b_open, _i32(1), K))
            wtake = take & w_lane
            w_tk = jnp.sum(wtake, dtype=jnp.int32)

            # -- GC cycle: every valid page of the victim relocates in
            # this one step (k <= ppb, so at most one block opens)
            v = s.victim
            win = jax.lax.dynamic_slice(s.p2l, (v * ppb,), (ppb,))
            vmask = in_gc & (win >= 0)
            k = jnp.sum(vmask, dtype=jnp.int32)
            r_idx = jnp.cumsum(vmask.astype(jnp.int32)) - 1
            glpn = jnp.clip(win, 0)

            # -- allocation (either path pops at most one free block)
            need_g = in_gc & (k > room)
            pop = b_open | need_g
            no_free = pop & (s.free_tail <= s.free_head)
            popped = s.free_q[s.free_head % blocks]
            open2 = jnp.where(pop, popped, s.open_block)
            np0 = jnp.where(b_open, _i32(0), s.next_page)
            next_page = jnp.where(
                in_host, np0 + w_tk,
                jnp.where(in_gc,
                          jnp.where(need_g, k - room, s.next_page + k),
                          s.next_page))
            free_head = s.free_head + pop.astype(jnp.int32)
            free_tail = s.free_tail + in_gc.astype(jnp.int32)
            seq = s.seq + pop.astype(jnp.int32)
            # block-array updates are dense predicated selects over
            # [blocks]: XLA:CPU lowers scatter to a scalar loop, so
            # rewriting a whole block-sized array elementwise beats
            # touching two elements by index.  The pop target and the
            # erased victim are always distinct blocks (a victim is
            # full — never the open block or a free one).
            was_open = pop & (barange == s.open_block)
            at_victim = in_gc & (barange == v)
            full = (s.full | was_open) & ~at_victim
            fill_seq = jnp.where(pop & (barange == popped), s.seq,
                                 jnp.where(at_victim, _i32(-1),
                                           s.fill_seq))
            erase_count = s.erase_count + at_victim.astype(jnp.int32)
            free_q = jnp.where(
                in_gc & (barange == s.free_tail % blocks), v, s.free_q)

            # -- burst mapping (`FTLState.map_write`, vectorised): the
            # last write of each lpn owns the final L2P entry; every
            # write invalidates its predecessor — the pre-burst mapping
            # for a first occurrence, the previous duplicate's in-burst
            # page otherwise
            nowhere = _i32(total)
            wppn = open2 * ppb + np0 + (w_cum - 1)
            eqm = ((wlpn[:, None] == wlpn[None, :])
                   & wtake[:, None] & wtake[None, :])
            after = lanes[:, None] < lanes[None, :]
            is_last = wtake & ~jnp.any(eqm & after, axis=1)
            is_first = wtake & ~jnp.any(eqm & ~after & not_eye, axis=1)
            prev = jnp.max(jnp.where(eqm & after.T, lanes[None, :], -1),
                           axis=1)
            old_lane = jnp.where(is_first, s.l2p[wlpn],
                                 wppn[jnp.clip(prev, 0)])
            has_old = wtake & (old_lane >= 0)
            old_c = jnp.clip(old_lane, 0)

            # -- cycle mapping: relocations fill the frontier, spilling
            # into the popped block.  XLA:CPU pays scatter cost *per
            # update row* (~50 ns each, batched or not — measured), so
            # the map updates are organised to minimise rows: burst and
            # cycle predicates are disjoint (`in_host` vs `in_gc`), so
            # each map takes one [ppb]-row lane-wise-select scatter for
            # new entries, and P2L's invalidations (host predecessors /
            # the victim window wipe) share a second.  Valid counts go
            # dense instead: a [ppb, blocks] one-hot histogram of
            # invalidated blocks plus predicated adds on [blocks] (the
            # victim zeroes by subtracting k — its count *is* k, the
            # popcount of its P2L window).
            gr_in = r_idx < room
            gppn = (jnp.where(gr_in, s.open_block, popped) * ppb
                    + jnp.where(gr_in, s.next_page + r_idx,
                                r_idx - room))
            l2p = s.l2p.at[jnp.where(
                in_gc, jnp.where(vmask, glpn, nowhere),
                jnp.where(is_last, wlpn, nowhere))].set(
                jnp.where(in_gc, gppn, wppn), mode="drop")
            p2l = s.p2l.at[jnp.where(
                in_gc, jnp.where(vmask, gppn, nowhere),
                jnp.where(wtake, wppn, nowhere))].set(
                jnp.where(in_gc, glpn, wlpn), mode="drop")
            p2l = p2l.at[jnp.where(
                in_gc, v * ppb + lanes,
                jnp.where(has_old, old_c, nowhere))].set(
                _i32(-1), mode="drop")
            old_hist = jnp.sum(
                has_old[:, None] & ((old_c // ppb)[:, None] == barange),
                axis=0, dtype=jnp.int32)
            vc = (s.valid_count - old_hist
                  + jnp.where(in_host & (barange == open2), w_tk, 0)
                  - jnp.where(at_victim, k, 0)
                  + jnp.where(in_gc & (barange == s.open_block),
                              jnp.minimum(k, room), 0)
                  + jnp.where(need_g & (barange == popped),
                              k - room, 0))

            in_gc_i = in_gc.astype(jnp.int32)
            guard = jnp.where(w_tk > 0, _i32(0), s.guard + in_gc_i)
            err = s.err | jnp.where(no_free, _i32(ERR_NO_FREE), _i32(0))
            err = err | jnp.where(in_gc & (guard > 4 * blocks),
                                  _i32(ERR_GUARD), _i32(0))

            # -- GC trigger + victim selection on the post-step arrays
            # (exactly the state the host's `while` loop re-tests: a
            # burst only triggers through its final write when the pool
            # already sat at the threshold, an allocating write or a
            # finished cycle re-checks the live pool).  The cascaded
            # masked argmin reproduces `np.lexsort`: min valid (greedy
            # only), then min fill_seq, then lowest block id.
            free_blocks = free_tail - free_head
            trigger = ((in_host & (w_tk > 0)) | in_gc) \
                & (free_blocks <= gc_free)
            any_c = jnp.any(full)
            m_valid = jnp.min(jnp.where(full, vc, _i32(_BIG)))
            c2 = full & (is_lru | (vc == m_valid))
            m_fill = jnp.min(jnp.where(c2, fill_seq, _i32(_BIG)))
            new_victim = jnp.argmax(c2 & (fill_seq == m_fill)).astype(
                jnp.int32)
            err = err | jnp.where(trigger & ~any_c, _i32(ERR_NO_CAND),
                                  _i32(0))
            err = err | jnp.where(trigger & any_c & (m_valid >= ppb),
                                  _i32(ERR_ALL_VALID), _i32(0))
            mode = jnp.where(active,
                             jnp.where(trigger, _i32(MODE_GC),
                                       _i32(MODE_HOST)), s.mode)
            victim = jnp.where(trigger, new_victim, s.victim)

            # -- counters + watermark (the host samples it after each
            # write that starts no GC drain, and after each erase)
            lastw = jnp.max(jnp.where(wtake, lanes, _i32(-1)))
            arrival = jnp.where(w_tk > 0, warr[jnp.clip(lastw, 0)],
                                s.arrival)
            free_now = free_blocks * ppb + (ppb - next_page)
            watermark = jnp.where(
                (in_host & (w_tk > 0) & ~trigger) | in_gc,
                jnp.minimum(s.watermark, free_now), s.watermark)
            kk = jnp.where(in_gc, k, _i32(0))
            s2 = ScanFTLState(
                l2p=l2p, p2l=p2l, valid_count=vc, full=full,
                fill_seq=fill_seq, erase_count=erase_count,
                free_q=free_q, free_head=free_head, free_tail=free_tail,
                open_block=open2, next_page=next_page, seq=seq,
                h=s.h + jnp.where(in_host,
                                  jnp.where(b_open, _i32(1), K), _i32(0)),
                mode=mode, victim=victim, guard=guard, arrival=arrival,
                watermark=watermark, host_w=s.host_w + w_tk,
                total_w=s.total_w + w_tk + kk,
                gc_pages=s.gc_pages + kk, gc_reads=s.gc_reads + kk,
                gc_writes=s.gc_writes + kk,
                erases=s.erases + in_gc_i, err=err)

            # -- emit one row: burst ops in lanes 0..ppb-1, the cycle's
            # read/write pairs at (2i, 2i+1) and its erase at lane 2k.
            # GC lanes carry no per-page payload — just op class, the
            # cycle's arrival and a valid bit — and host/GC predicates
            # are disjoint, so the whole row is elementwise selects on
            # the lane index (no scatter); idle lanes are the
            # payload-masked identity.
            gc_val = in_gc & (jlanes <= 2 * k)
            gc_cls = jnp.where(jlanes < 2 * k, gc_pat, _i32(ERASE))
            h_cls = jnp.concatenate([
                jnp.where(take,
                          jnp.where(w_lane, _i32(FTL_WRITE),
                                    _i32(FTL_READ)), _i32(0)),
                jnp.zeros((ppb + 1,), jnp.int32)])
            h_arr = jnp.concatenate([
                jnp.where(take, warr, jnp.float32(0.0)),
                jnp.zeros((ppb + 1,), jnp.float32)])
            row_cls = jnp.where(gc_val, gc_cls, h_cls)
            row_arr = jnp.where(gc_val, s.arrival, h_arr)
            row_pay = jnp.concatenate([take & wpay,
                                       jnp.zeros((ppb + 1,), bool)])
            row_rid = jnp.concatenate([
                jnp.where(take, wrid, _i32(-1)),
                jnp.full((ppb + 1,), -1, jnp.int32)])
            row_val = gc_val | jnp.concatenate(
                [take, jnp.zeros((ppb + 1,), bool)])
            return s2, (row_cls, row_arr, row_pay, row_rid, row_val)

        state = state._replace(
            l2p=jnp.asarray(state.l2p, jnp.int32),
            p2l=jnp.asarray(state.p2l, jnp.int32))
        return jax.lax.scan(step, state, None, length=t_max,
                            unroll=unroll)

    return fold


#: Scan unroll factor for the jitted folds.  Measured on XLA:CPU the
#: fold is dispatch-dominated *inside* the step (scatter/gather ops),
#: so unrolling the scan body buys nothing (424 us/step at unroll 1,
#: 2 and 4 alike) — keep 1 for the smallest compile.
_UNROLL = 1


@functools.lru_cache(maxsize=128)
def _jitted_fold(blocks: int, ppb: int, n_host: int, t_max: int):
    return jax.jit(make_translate_fold(blocks, ppb, n_host, t_max,
                                       unroll=_UNROLL))


def _bucket(n: int, floor: int = 64) -> int:
    """Quantise ``n`` up to an eight-steps-per-octave ladder (multiples
    of ``2^(ceil(log2 n) - 3)``, <= ~14% slack).  Power-of-two buckets
    would waste up to 2x: the fold's wall time is linear in ``t_max``,
    so buffer slack is pure cost, while each extra ladder point is at
    most one more compile (``_jitted_fold`` keys on bucketed shapes)."""
    n = max(n, floor)
    base = 1 << max((n - 1).bit_length() - 3, 0)
    return -(-n // base) * base


def _est_waf(spec: FTLSpec) -> float:
    """Estimated steady-state WAF with a policy safety margin (lru
    decays worse than the greedy fixed point)."""
    return analytic_waf(spec.utilization) * (
        1.15 if spec.gc_policy == "greedy" else 2.5)


def estimate_t_max(spec: FTLSpec, n_reads: int, n_writes: int, *,
                   precondition: bool = False) -> int:
    """Initial output-buffer length in fused *steps*.  Calibrated
    against measured machine runs: at steady state every GC cycle
    costs ~3 rows on a mixed stream (the cycle itself, the allocating
    write that fired it, and the burst fragment it cut), while the
    preconditioning stream (a sequential fill, then uniform
    overwrites, ``precondition=True``) fragments less (~2 rows per
    cycle — its fill phase runs GC-free).  An underestimate is
    detected, not wrong — the caller doubles and re-runs, and the
    sweep path remembers the realised row count per shape."""
    ppb = spec.pages_per_block
    n = n_reads + n_writes
    cycles = math.ceil(n_writes * _est_waf(spec) / ppb)
    rows_per_cycle = 2 if precondition else 3
    return _bucket(-(-n // ppb) + -(-n_writes // ppb)
                   + rows_per_cycle * cycles + spec.blocks // ppb + 32)


def estimate_ops(spec: FTLSpec, n_reads: int, n_writes: int) -> int:
    """Physical op-count estimate for one translated stream (host ops
    plus GC read/write pairs plus erases) — the sweep path's initial
    compacted end-time buffer length (unbucketed; the caller pads and
    buckets, and doubles on overflow)."""
    w = _est_waf(spec)
    ppb = spec.pages_per_block
    gc_pages = math.ceil(n_writes * max(w - 1.0, 0.0))
    erases = math.ceil(n_writes * w / ppb) + spec.blocks
    return n_reads + n_writes + 2 * gc_pages + erases


_ERR_ORDER = (ERR_NO_FREE, ERR_GUARD, ERR_NO_CAND, ERR_ALL_VALID)


def _raise_scan_error(err: int, spec: FTLSpec):
    """Decode a latched error bit to the host translator's message,
    verbatim (the check order mirrors which raise the host loop
    reaches first)."""
    msgs = {
        ERR_NO_FREE: "FTL out of free blocks mid-allocation — geometry "
                     f"too small for GC to keep up ({spec.describe()})",
        ERR_GUARD: "GC cannot reclaim space — overprovisioning too "
                   f"small for the footprint ({spec.describe()})",
        ERR_NO_CAND: "GC triggered with no collectable block "
                     f"({spec.describe()}) — grow blocks or "
                     "gc_free_blocks",
        ERR_ALL_VALID: "every collectable block is fully valid — the "
                       "logical footprint has consumed the "
                       f"overprovisioning pool ({spec.describe()}); "
                       "raise overprovision or shrink the workload "
                       "footprint",
    }
    for bit in _ERR_ORDER:
        if err & bit:
            raise RuntimeError(msgs[bit])
    raise RuntimeError(f"unknown FTL scan error bits {err}")


def _run_machine(fs: ScanFTLState, spec: FTLSpec, cls, arr, pay, rid,
                 lpns, t_hint: int, headroom: int = 0):
    """Run the translation machine over one host-op batch, doubling the
    output buffer until the stream is fully consumed.  Returns
    ``(final_state, ys, runs)`` with ``ys`` the raw ``[t_max, 2*ppb+1]``
    emission rows (``_trim`` flattens and masks them) and ``runs`` the
    launches of the fold (1 when ``t_hint`` held).  ``headroom`` extra
    host slots go into the bucket the host arrays are padded to."""
    n = len(cls)
    ppb = spec.pages_per_block
    n_b = _bucket(n + ppb + headroom)   # window slack: the ppb-op host
    pad = n_b - n                       # slice at h never clamps
    cls_p = np.pad(np.asarray(cls, np.int32), (0, pad))
    arr_p = np.pad(np.asarray(arr, np.float32), (0, pad))
    pay_p = np.pad(np.asarray(pay, bool), (0, pad))
    rid_p = np.pad(np.asarray(rid, np.int32), (0, pad))
    lpn_p = np.pad(np.asarray(lpns, np.int32), (0, pad))
    gc_free = np.int32(spec.gc_free_blocks)
    is_lru = spec.gc_policy == "lru"
    t_max = _bucket(t_hint)
    # hard ceiling: the guard bounds GC cycles per host write and every
    # step consumes a host op or runs a cycle, so a complete run can
    # never need more steps than this
    cap = 2 * _bucket(n * (4 * spec.blocks + 2) + 64)
    fs = fs._replace(h=_i32(0))
    runs = 0
    while True:
        runs += 1
        fold = _jitted_fold(spec.blocks, ppb, n_b, t_max)
        out, ys = fold(cls_p, arr_p, pay_p, rid_p, lpn_p, np.int32(n),
                       gc_free, is_lru, fs)
        err = int(out.err)
        if err:
            _raise_scan_error(err, spec)
        if int(out.h) >= n and int(out.mode) == MODE_HOST:
            return out, ys, runs
        if t_max >= cap:     # pragma: no cover - guard catches first
            raise RuntimeError(
                "FTL scan translation failed to terminate "
                f"({spec.describe()})")
        t_max *= 2


def _trim(ys) -> tuple[np.ndarray, ...]:
    op_cls, arrival, payload, rid, valid = ys
    m = np.asarray(valid).reshape(-1)
    cls = np.asarray(op_cls, np.int32).reshape(-1)[m]
    return (cls,
            np.asarray(arrival, np.float32).reshape(-1)[m],
            np.asarray(payload, bool).reshape(-1)[m],
            np.asarray(rid, np.int32).reshape(-1)[m],
            cls >= GC_READ)


def _reset_window(fs: ScanFTLState, ppb: int) -> ScanFTLState:
    """Zero the measured-window counters after preconditioning (wear —
    ``erase_count`` — persists), mirroring ``ftl._precondition``.
    Shape-polymorphic: works on a single state or a stacked ``[P]``
    batch of them (the sweep path's cached pre-states)."""
    free_now = ((fs.free_tail - fs.free_head) * ppb
                + (ppb - fs.next_page))
    z = jnp.zeros_like(fs.host_w)
    return fs._replace(host_w=z, total_w=z, gc_pages=z, gc_reads=z,
                       gc_writes=z, erases=z,
                       watermark=_i32(free_now), h=z)


def rmw_mask(cls, lpns, partial, mapped) -> np.ndarray:
    """[T] True at each write that reads its page's old copy first: a
    write that covers its page in part (``partial``) to a logical page
    that is ``mapped`` (indexed by lpn) before the stream or written
    earlier in it."""
    writes = np.flatnonzero(np.asarray(cls) == WRITE)
    lp = np.asarray(lpns, np.int64)[writes]
    _, first = np.unique(lp, return_index=True)
    earlier = np.ones(len(writes), bool)
    earlier[first] = False
    out = np.zeros(len(cls), bool)
    out[writes] = partial[writes] & (earlier | np.asarray(mapped)[lp])
    return out


def _with_rmw_reads(rmw, cls, arr, pay, rid, lpns):
    """The host op arrays with a payload-free read of the same request
    inserted before each ``rmw`` write."""
    src = np.repeat(np.arange(len(cls)), 1 + rmw.astype(np.int64))
    first = np.ones(len(src), bool)
    first[1:] = src[1:] != src[:-1]
    read = first & rmw[src]
    return (np.where(read, READ, np.asarray(cls)[src]).astype(np.int32),
            np.asarray(arr)[src], np.asarray(pay)[src] & ~read,
            np.asarray(rid)[src], np.asarray(lpns)[src])


def translate_scan(stream: RequestStream, spec: FTLSpec, *,
                   state: FTLState | None = None,
                   page_bytes: int | None = None) -> FTLTranslation:
    """``ftl.translate`` compiled: identical op sequence, stats and
    final drive state for every fault-free translation, via the
    ``lax.scan`` machine instead of the per-op host loop.  ``state``
    chains aging exactly like the host path, except the input state is
    *not* mutated — use the returned ``FTLTranslation.state``.  Block-
    level fault probabilities are not accepted here (RNG stays outside
    the folds); ``repro.core.api`` routes faulty translations to the
    host oracle.  ``page_bytes`` prices ``FTLStats.host_bytes_written``
    as in ``ftl.translate``."""
    if stream.n_requests == 0:
        raise ValueError("empty workload: no requests to translate")
    if int(np.max(stream.op_cls)) > WRITE:
        raise ValueError(
            "FTL translation consumes host READ/WRITE streams only "
            f"(got op class {int(np.max(stream.op_cls))})")
    prev = FTLStats() if state is None else state.stats
    with _spans.span("translate") as sp:
        runs = 0
        if state is None:
            fs = scan_state_fresh(spec)
            if spec.precondition:
                with _spans.span("precondition") as pre:
                    lp = precondition_lpns(spec)
                    npre = len(lp)
                    fs, _, runs = _run_machine(
                        fs, spec, np.full(npre, WRITE, np.int32),
                        np.zeros(npre, np.float32), np.zeros(npre, bool),
                        np.full(npre, -1, np.int32), lp,
                        estimate_t_max(spec, 0, npre, precondition=True))
                    fs = _reset_window(fs, spec.pages_per_block)
                    pre.set_metadata(writes=npre, machine_runs=runs)
        else:
            fs = scan_state_from_host(state)
        # the machine runs the state's own spec (a chained state owns
        # the drive); the host-facing address space stays the caller's,
        # exactly like the host path's request_lpns call
        mspec = spec if state is None else state.spec
        cls, arrival, rid, payload = request_ops(stream)
        lpns = request_lpns(stream, spec.logical_pages)
        n_host = len(cls)
        partial = request_partial(stream, spec.sectors_per_page) \
            & (cls == WRITE)
        n_rmw = 0
        if partial.any():
            mapped = np.asarray(fs.l2p) >= 0
            rmw = rmw_mask(cls, lpns, partial, mapped)
            n_rmw = int(rmw.sum())
            cls, arrival, payload, rid, lpns = _with_rmw_reads(
                rmw, cls, arrival, payload, rid, lpns)
        n_writes = int(np.sum(cls == WRITE))
        # a sector stream's host-op count (pages plus RMW reads) moves
        # with its draw of transfer sizes: 1/16 headroom keeps draws of
        # one size on one compiled machine
        fs, ys, n_runs = _run_machine(
            fs, mspec, cls, arrival, payload, rid, lpns,
            estimate_t_max(mspec, len(cls) - n_writes, n_writes),
            headroom=len(cls) // 16 if stream.sectored else 0)
        op_cls, arr, pay, rid_o, gc = _trim(ys)
        out_state = scan_state_to_host(fs, mspec)
        out_state.stats.rmw_reads = prev.rmw_reads + n_rmw
        out_state.stats.host_bytes_written = (
            prev.host_bytes_written
            + host_write_bytes(stream, spec, page_bytes))
        sp.set_metadata(host_ops=n_host, out_ops=len(op_cls),
                        machine_runs=runs + n_runs, rmw_reads=n_rmw,
                        partial_pages=int(np.sum(partial)),
                        host_sectors=stream.host_sectors(
                            spec.sectors_per_page))
    return FTLTranslation(op_cls=op_cls, arrival_us=arr, payload=pay,
                          request_id=rid_o, gc=gc,
                          stats=out_state.stats, state=out_state)


__all__ = [
    "ERR_ALL_VALID", "ERR_GUARD", "ERR_NO_CAND", "ERR_NO_FREE",
    "MODE_GC", "MODE_HOST",
    "ScanFTLState", "estimate_ops", "estimate_t_max",
    "make_translate_fold", "rmw_mask",
    "scan_state_fresh", "scan_state_from_host", "scan_state_to_host",
    "translate_scan",
]
