"""Benchmark: fleet-scale simulation throughput (DESIGN.md §2.7).

Three scaling axes of the serving path are measured and gated:

* **fused megakernel** — ``Simulator.run_many(engine="pallas")``
  evaluates a whole fleet of heterogeneous traces as ONE Pallas launch
  (lanes = traces, union combo dictionary, identity-padded lengths);
  it must beat the per-trace launch loop (the pre-fusion serving path,
  one ``pallas_call`` per trace) by >= 2x at T = 2048 in a full run,
  and agree with the scan engine < 1e-3 always (smoke included);
* **streaming engine** — ``Simulator.run_stream`` folds a >= 1M-op
  generated trace in fixed-size chunks with the occupancy state carried
  between chunks; the full run asserts the Python-side peak allocation
  is set by the chunk size, not the trace length (flat across a 4x
  longer trace, and well under materialising it), and every run asserts
  < 1e-3 agreement with the event-loop oracle on an overlapping size;
* **shard_map sweeps** — with more than one device in the process,
  times the design-point sweep with the table batch sharded across
  devices vs the single-device vmap path and asserts bit-equal results
  (reported, not gated).  It runs in-process: a child that imports JAX
  would contend for the chip its parent holds.
"""

from __future__ import annotations

import time
import tracemalloc

import jax
import numpy as np

from repro.api import Simulator, SSDConfig, sweep_tables
from repro.core.nand import CellType
from repro.core.sim_ref import simulate_trace_ref
from repro.core.trace import mixed_trace, mixed_trace_chunks

T_FLEET = 2048        # acceptance gate: megakernel must win here
N_FLEET = 24
N_STREAM = 1_000_000  # acceptance gate: million-op trace, constant memory
CHUNK = 32_768


def _rel(a: float, b: float) -> float:
    return abs(a - b) / abs(b)


def run_megakernel(small: bool = False) -> list[dict]:
    t_ops = 256 if small else T_FLEET
    n_fleet = 6 if small else N_FLEET
    cfg = SSDConfig(cell=CellType.MLC, channels=2, ways=8)
    sim = Simulator(cfg)
    fleet = [mixed_trace(t_ops, 2, 8, read_fraction=0.5, seed=i)
             for i in range(n_fleet)]

    from repro.kernels.maxplus.ops import (run_many_end_time_maxplus,
                                           trace_end_time_maxplus)

    def per_trace():
        return [float(trace_end_time_maxplus(sim.table, t)) for t in fleet]

    def fused():
        return run_many_end_time_maxplus(sim.table, fleet)

    loop_ends = per_trace()            # warm both compiled shapes
    fused_ends = fused()
    t0 = time.perf_counter()
    loop_ends = per_trace()
    t_loop = time.perf_counter() - t0
    t0 = time.perf_counter()
    fused_ends = fused()
    t_fused = time.perf_counter() - t0

    scan_ends = [r.end_us for r in sim.run_many(fleet)]
    agree = max(max(_rel(a, s) for a, s in zip(loop_ends, scan_ends)),
                max(_rel(a, s) for a, s in zip(fused_ends, scan_ends)))
    assert agree < 1e-3, \
        f"megakernel disagrees with scan by {agree:.2e} at T={t_ops}"
    speedup = t_loop / max(t_fused, 1e-9)
    if not small:
        assert speedup >= 2.0, \
            f"megakernel speedup {speedup:.2f}x < 2x over per-trace " \
            f"launches (fleet={n_fleet}, T={t_ops})"
    return [
        {"name": f"scale/megakernel_T{t_ops}_B{n_fleet}/per_trace_ms",
         "value": round(t_loop * 1e3, 1), "paper": "-"},
        {"name": f"scale/megakernel_T{t_ops}_B{n_fleet}/fused_ms",
         "value": round(t_fused * 1e3, 1), "paper": "-"},
        {"name": f"scale/megakernel_T{t_ops}_B{n_fleet}/speedup",
         "value": round(speedup, 1), "paper": ">=2"},
        {"name": "scale/megakernel_vs_scan_rel",
         "value": f"{agree:.1e}", "paper": "<1e-3"},
    ]


def run_streaming(small: bool = False) -> list[dict]:
    n_ops = 65_536 if small else N_STREAM
    chunk = 8_192 if small else CHUNK
    cfg = SSDConfig(cell=CellType.MLC, channels=4, ways=8)
    sim = Simulator(cfg)

    # warm the chunk-shape closures outside the traced windows
    sim.run_stream(mixed_trace_chunks(2 * chunk, 4, 8, 0.5,
                                      chunk_len=chunk, seed=1))

    def peak_of(n):
        tracemalloc.start()
        t0 = time.perf_counter()
        res = sim.run_stream(mixed_trace_chunks(n, 4, 8, 0.5,
                                                chunk_len=chunk, seed=2))
        dt = time.perf_counter() - t0
        _, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        assert res.n_ops == n
        return peak / 1e6, dt

    peak_small_mb, _ = peak_of(n_ops // 4)
    peak_mb, t_stream = peak_of(n_ops)
    # constant memory = the peak is set by the chunk size, not the trace
    # length: quadrupling the op count must leave the Python-side peak
    # essentially flat (and far below materialising the ~6 int32/float32
    # columns of the whole trace)
    full_mb = n_ops * 6 * 4 / 1e6
    if not small:
        assert peak_mb < 1.5 * peak_small_mb + 1.0, \
            f"streaming peak grew {peak_small_mb:.1f} -> {peak_mb:.1f} MB " \
            f"over a 4x longer trace — not constant-memory"
        assert peak_mb < full_mb / 2, \
            f"streaming peak {peak_mb:.1f} MB vs full trace {full_mb:.1f} " \
            f"MB — not constant-memory"

    # overlapping-size agreement vs the event-loop oracle (always gated)
    t_small = 512 if small else 4096
    probe = mixed_trace(t_small, 4, 8, 0.5, seed=3)
    want = simulate_trace_ref(sim.table, probe, "eager")
    got = sim.run(probe, engine="streaming").end_us
    agree = _rel(got, want)
    assert agree < 1e-3, \
        f"streaming disagrees with oracle by {agree:.2e} at T={t_small}"
    return [
        {"name": f"scale/stream_{n_ops}ops/wall_s",
         "value": round(t_stream, 2), "paper": "-"},
        {"name": f"scale/stream_{n_ops}ops/ops_per_s",
         "value": int(n_ops / t_stream), "paper": "-"},
        {"name": f"scale/stream_{n_ops}ops/py_peak_mb",
         "value": round(peak_mb, 1),
         "paper": f"<{full_mb / 2:.0f}" if not small else "-"},
        {"name": f"scale/stream_{n_ops // 4}ops/py_peak_mb",
         "value": round(peak_small_mb, 1), "paper": "-"},
        {"name": "scale/stream_vs_oracle_rel",
         "value": f"{agree:.1e}", "paper": "<1e-3"},
    ]


def run_shard(small: bool = False) -> list[dict]:
    """Sharded sweep == vmap sweep on the devices this process holds,
    timed.  One device has no mesh to shard over: no rows."""
    n_dev = len(jax.devices())
    if n_dev < 2:
        print("# scale/shard_sweep: one device, sharded sweep skipped")
        return []
    b = 16 if small else 64
    t_ops = 128 if small else 512
    sim = Simulator(SSDConfig(cell=CellType.MLC, channels=2, ways=8))
    trace = mixed_trace(t_ops, 2, 8, read_fraction=0.5, seed=5)
    tabs = [sim.table] * b
    times = {}
    for shard in (True, False):
        sweep_tables(tabs, trace, engine="scan", shard=shard)   # warm
        t0 = time.perf_counter()
        out = np.asarray(sweep_tables(tabs, trace, engine="scan",
                                      shard=shard))
        times[shard] = (time.perf_counter() - t0, out)
    assert np.array_equal(times[True][1], times[False][1]), \
        "shard_map != vmap"
    return [
        {"name": f"scale/shard_sweep_B{b}_T{t_ops}/devices",
         "value": n_dev, "paper": "-"},
        {"name": f"scale/shard_sweep_B{b}_T{t_ops}/shard_map_ms",
         "value": round(times[True][0] * 1e3, 1), "paper": "-"},
        {"name": f"scale/shard_sweep_B{b}_T{t_ops}/vmap_ms",
         "value": round(times[False][0] * 1e3, 1), "paper": "-"},
        {"name": f"scale/shard_sweep_B{b}_T{t_ops}/agreement",
         "value": "bit-equal", "paper": "="},
    ]


def run(small: bool = False) -> list[dict]:
    return (run_megakernel(small) + run_streaming(small)
            + run_shard(small))
