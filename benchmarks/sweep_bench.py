"""Benchmark: design-space sweep throughput (paper's simulator, modernised).

The paper evaluates ~60 design points with an RTL co-simulation.  This
framework's contribution is making that sweep a data-parallel tensor
program: we time (a) the plain-Python event loop, (b) the jit+vmap
``lax.scan`` engine, (c) the (max,+) Pallas kernel (interpreted
off-TPU; its compiled TPU speed is not measured here), and (d) the **log-depth engines** (DESIGN.md §2.3) — periodic matrix
squaring for the homogeneous grid and the segmented parallel-prefix
fold for heterogeneous traces — over a channels × ways × interface ×
cell × mode grid and over mixed-workload op traces.  ``run_logdepth``
pushes the trace length to T >= 2048, where the O(log T) engines must
beat the O(T) scan per design point (the speedup rows asserted by
``benchmarks/run_all.py`` / CI).  Every query dispatches through the
``repro.api`` registry/``Simulator`` sessions, so the engine-agreement
gate exercises the unified serving surface (the repeated-query cache
benchmark itself lives in ``benchmarks/api_bench.py``)."""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.api import Simulator, sweep_steady_bandwidth_mb_s, sweep_tables
from repro.core.energy import breakdown_from_sums
from repro.core.interface import InterfaceKind, make_interface
from repro.core.nand import CellType, chip
from repro.core.sim import SSDConfig, page_op_params
from repro.core.sim_ref import (bandwidth_ref_mb_s,
                                simulate_trace_energy_ref,
                                trace_bandwidth_ref_mb_s)
from repro.core.trace import mixed_trace
from repro.kernels.maxplus.ops import (bandwidth_maxplus_mb_s,
                                       trace_bandwidth_maxplus_mb_s)

N_PAGES = 256
T_LOGDEPTH = 2048     # acceptance gate: log-depth engines must win here


def _grid():
    ops, ways = [], []
    for kind in InterfaceKind:
        for cell in CellType:
            for mode in ("read", "write"):
                for w in (1, 2, 4, 8, 16):
                    ops.append(page_op_params(make_interface(kind), chip(cell),
                                              mode, w))
                    ways.append(w)
    return ops, ways


def _sweep_args(ops):
    return tuple(jnp.array([getattr(o, f) for o in ops], jnp.float32)
                 for f in ("cmd_us", "pre_us", "slot_us", "post_lo_us",
                           "post_hi_us", "ctrl_us", "data_bytes"))


def run(small: bool = False) -> list[dict]:
    n_pages = 64 if small else N_PAGES
    ops, ways = _grid()
    n = len(ops)

    t0 = time.perf_counter()
    ref = np.array([bandwidth_ref_mb_s(o, w, n_pages) for o, w in zip(ops, ways)])
    t_ref = time.perf_counter() - t0

    args = _sweep_args(ops)
    wv = jnp.array(ways, jnp.int32)
    sweep_steady_bandwidth_mb_s(
        *args, wv, n_pages=n_pages).block_until_ready()           # compile
    t0 = time.perf_counter()
    vm = np.asarray(sweep_steady_bandwidth_mb_s(*args, wv, n_pages=n_pages))
    t_vm = time.perf_counter() - t0

    t0 = time.perf_counter()
    mp = bandwidth_maxplus_mb_s(ops, ways, n_pages=n_pages)
    t_mp = time.perf_counter() - t0

    assert np.allclose(ref, vm, rtol=1e-3)
    assert np.allclose(ref, mp, rtol=1e-3)
    return [
        {"name": "sweep/python_event_loop_us_per_point",
         "value": round(t_ref / n * 1e6, 1), "paper": "-"},
        {"name": "sweep/jit_vmap_scan_us_per_point",
         "value": round(t_vm / n * 1e6, 1), "paper": "-"},
        {"name": "sweep/maxplus_interpret_us_per_point",
         "value": round(t_mp / n * 1e6, 1),
         "paper": "(interpret mode; TPU not measured)"},
        {"name": "sweep/vmap_speedup_vs_python",
         "value": round(t_ref / max(t_vm, 1e-9), 1), "paper": "-"},
    ] + run_mixed(small) + run_logdepth(small)


def run_mixed(small: bool = False) -> list[dict]:
    """Mixed-workload design-point sweep (beyond the paper's §5.3 grid):
    read fraction × (channels, ways), all three engines on one trace per
    geometry, batching interfaces×cells through the (max,+) kernel.
    Each point also carries its phase-resolved controller energy
    (DESIGN.md §2.4), gated on cross-engine agreement like the
    bandwidths."""
    n_pages = 64 if small else N_PAGES
    rows, agree, agree_e = [], 0.0, 0.0
    n_points = 0
    t_scan = t_mp = t_ref = 0.0
    for channels, ways in ((1, 8), (2, 4), (4, 8)):
        for read_frac in (1.0, 0.7, 0.5, 0.0):
            tr = mixed_trace(n_pages * channels, channels, ways, read_frac,
                             seed=channels * 100 + int(read_frac * 10))
            cfgs = [SSDConfig(interface=k, cell=c, channels=channels,
                              ways=ways)
                    for k in InterfaceKind for c in CellType]
            sims = [Simulator.for_config(cfg) for cfg in cfgs]
            tables = [s.table for s in sims]
            t0 = time.perf_counter()
            scan_bw = np.array([s.run(tr, objective="bandwidth").mb_s
                                for s in sims])
            t_scan += time.perf_counter() - t0
            t0 = time.perf_counter()
            mp_bw = trace_bandwidth_maxplus_mb_s(tables, tr)
            t_mp += time.perf_counter() - t0
            t0 = time.perf_counter()
            ref_bw = np.array([trace_bandwidth_ref_mb_s(t, tr)
                               for t in tables])
            t_ref += time.perf_counter() - t0
            agree = max(agree,
                        float(np.max(np.abs(scan_bw - ref_bw) / ref_bw)),
                        float(np.max(np.abs(mp_bw - ref_bw) / ref_bw)))
            n_points += len(tables)
            # phase-resolved energy of the PROPOSED/MLC point, all three
            # engines vs the event-loop oracle (heterogeneous-trace half
            # of the energy smoke gate; Table 5 covers the steady half)
            kind = InterfaceKind.PROPOSED
            bds = {eng: sims[-1].run(tr, objective="energy",
                                     engine=eng).energy
                   for eng in ("scan", "prefix", "pallas")}
            end_e, sums_e = simulate_trace_energy_ref(tables[-1], tr, kind)
            ref_bd = breakdown_from_sums(sums_e, end_e,
                                         tr.total_bytes(tables[-1]), kind,
                                         channels=channels)
            agree_e = max(agree_e, *(
                abs(bd.controller_j - ref_bd.controller_j)
                / ref_bd.controller_j for bd in bds.values()))
            name = (f"mixed/{channels}ch{ways}way/"
                    f"read{int(read_frac * 100)}")
            rows.append({"name": f"{name}/proposed_mlc_mb_s",
                         "value": round(float(scan_bw[-1]), 1),
                         "paper": "-"})
            rows.append({"name": f"{name}/proposed_mlc_nj_per_byte",
                         "value": round(bds["scan"].nj_per_byte, 3),
                         "paper": "-",
                         "idle_frac": round(bds["scan"].idle_j
                                            / bds["scan"].controller_j, 4)})
    assert agree < 1e-3, f"engines disagree by {agree:.2e} on mixed traces"
    assert agree_e < 1e-3, \
        f"energy engines disagree by {agree_e:.2e} on mixed traces"
    rows += [
        {"name": "mixed/engine_max_rel_disagreement", "value": f"{agree:.1e}",
         "paper": "<1e-3"},
        {"name": "mixed/energy_engine_max_rel_disagreement",
         "value": f"{agree_e:.1e}", "paper": "<1e-3"},
        {"name": "mixed/scan_us_per_point",
         "value": round(t_scan / n_points * 1e6, 1), "paper": "-"},
        {"name": "mixed/maxplus_interpret_us_per_point",
         "value": round(t_mp / n_points * 1e6, 1), "paper": "-"},
        {"name": "mixed/python_oracle_us_per_point",
         "value": round(t_ref / n_points * 1e6, 1), "paper": "-"},
    ]
    return rows


def run_logdepth(small: bool = False) -> list[dict]:
    """Old-vs-new engine timings at long horizons (DESIGN.md §2.3).

    Homogeneous: the 60-point paper grid at T pages per point, O(T) scan
    vs O(log T) periodic squaring.  Heterogeneous: one mixed trace of T
    ops on a 2ch×8way geometry under interfaces×cells tables, per-point
    scan vs the segmented parallel-prefix engines.  Both speedup rows
    must exceed 1 at T >= 2048 and every engine must agree with the
    python oracle to 1e-3 — ``run_all.py`` (and the CI smoke step)
    asserts both."""
    t_pages = 256 if small else T_LOGDEPTH
    ops, ways = _grid()
    n = len(ops)
    args = _sweep_args(ops)
    wv = jnp.array(ways, jnp.int32)

    def timed(fn, reps=3):
        out = fn()
        out.block_until_ready()                      # compile
        dt = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn().block_until_ready()
            dt = min(dt, time.perf_counter() - t0)
        return np.asarray(out), dt

    scan_bw, t_scan = timed(lambda: sweep_steady_bandwidth_mb_s(
        *args, wv, n_pages=t_pages))
    sq_bw, t_sq = timed(lambda: sweep_steady_bandwidth_mb_s(
        *args, wv, n_pages=t_pages, engine="squaring"))
    agree = float(np.max(np.abs(sq_bw - scan_bw) / scan_bw))
    # python oracle on a few spot points (full grid at this T is slow)
    for i in (0, n // 2, n - 1):
        want = bandwidth_ref_mb_s(ops[i], ways[i], t_pages)
        agree = max(agree, abs(float(sq_bw[i]) - want) / want)
    assert agree < 1e-3, f"squaring disagrees by {agree:.2e} at T={t_pages}"

    rows = [
        {"name": f"logdepth/homog_T{t_pages}/scan_us_per_point",
         "value": round(t_scan / n * 1e6, 1), "paper": "-"},
        {"name": f"logdepth/homog_T{t_pages}/squaring_us_per_point",
         "value": round(t_sq / n * 1e6, 1), "paper": "-"},
        {"name": f"logdepth/homog_T{t_pages}/squaring_speedup_vs_scan",
         "value": round(t_scan / max(t_sq, 1e-9), 2), "paper": ">1"},
        {"name": f"logdepth/homog_T{t_pages}/max_rel_disagreement",
         "value": f"{agree:.1e}", "paper": "<1e-3"},
    ]

    # heterogeneous: one long mixed trace, batch of design-point tables
    channels, ways_h = 2, 8
    tr = mixed_trace(t_pages, channels, ways_h, 0.7, seed=42)
    sims = [Simulator.for_config(SSDConfig(interface=k, cell=c,
                                           channels=channels, ways=ways_h))
            for k in InterfaceKind for c in CellType]
    tables = [s.table for s in sims]
    b = len(tables)
    seg_len = 128

    def timed_np(fn, reps=3):
        out = fn()                                   # compile
        dt = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fn()
            dt = min(dt, time.perf_counter() - t0)
        return np.asarray(out), dt

    scan_us, t_scan_h = timed_np(
        lambda: np.array([s.run(tr).end_us for s in sims]))
    scanb_us, t_scanb = timed_np(
        lambda: sweep_tables(tables, tr, engine="scan"))
    px_us, t_px = timed_np(
        lambda: sweep_tables(tables, tr, engine="prefix",
                             segment_len=seg_len))

    from repro.kernels.maxplus.ops import trace_end_time_maxplus
    seg_us, t_seg = timed_np(
        lambda: trace_end_time_maxplus(tables, tr, strategy="segmented"),
        reps=1)                                      # dense: slow on CPU

    from repro.core.sim_ref import simulate_trace_ref
    ref_us = np.array([simulate_trace_ref(t, tr) for t in tables])
    agree_h = max(float(np.max(np.abs(e - ref_us) / ref_us))
                  for e in (scan_us, scanb_us, px_us, seg_us))
    assert agree_h < 1e-3, \
        f"trace engines disagree by {agree_h:.2e} at T={t_pages}"

    rows += [
        {"name": f"logdepth/mixed_T{t_pages}/scan_us_per_point",
         "value": round(t_scan_h / b * 1e6, 1), "paper": "-"},
        {"name": f"logdepth/mixed_T{t_pages}/scan_batch_us_per_point",
         "value": round(t_scanb / b * 1e6, 1), "paper": "-"},
        {"name": f"logdepth/mixed_T{t_pages}/prefix_batch_us_per_point",
         "value": round(t_px / b * 1e6, 1), "paper": "-"},
        {"name": f"logdepth/mixed_T{t_pages}/dense_segmented_us_per_point",
         "value": round(t_seg / b * 1e6, 1),
         "paper": "(MXU-shaped; TPU not measured)"},
        {"name": f"logdepth/mixed_T{t_pages}/prefix_speedup_vs_scan",
         "value": round(t_scan_h / max(t_px, 1e-9), 2), "paper": ">1"},
        {"name": f"logdepth/mixed_T{t_pages}/prefix_speedup_vs_scan_batch",
         "value": round(t_scanb / max(t_px, 1e-9), 2), "paper": "-"},
        {"name": f"logdepth/mixed_T{t_pages}/max_rel_disagreement",
         "value": f"{agree_h:.1e}", "paper": "<1e-3"},
    ]
    return rows
