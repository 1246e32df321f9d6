"""Bring-up smoke: the DDR-NAND SSD simulator's main path on a TPU.

    python3 chip_smoke.py              # one chip: every phase below
    python3 chip_smoke.py --chips 4    # the sharded sweeps only, on 4 chips

Every phase runs through ``repro.api.Simulator`` at drive and trace sizes
users of a DDR-NAND SSD model run, and checks its answer against an
independent reference; any failed check raises and the script exits
non-zero.  Inputs come from seeds.  Each phase prints one JSON line with
its shapes, the first-call (compile + run) seconds, the warm seconds and
the device.  The last line is ``{"ok": true, "device": {...}}``.

Phases on one chip:

* ``stream`` — the paper's widest drive (PROPOSED DDR interface, SLC,
  4 channels x 16 ways) folds a 1,048,576-op mixed stream through
  ``run_stream``; end time and energy must equal the scan engine on the
  materialised trace bit for bit, and a 16,384-op prefix must agree with
  the ``core/sim_ref`` event-loop oracle.
* ``sweep`` — 64 design points (interface x cell x ways) over a
  65,536-op trace with the default prefix engine against the scan engine.
* ``latency`` — 65,536 Poisson requests under ``least_loaded`` dynamic
  dispatch and static ``stripe`` (p50/p99); stripe against the prefix
  engine.
* ``ftl`` — an aged drive of 4096 blocks x 64 pages (262,144 pages,
  12% overprovisioning, greedy GC, preconditioned) under 131,072 uniform
  overwrites: greedy WAF against the analytic fixed point, the scan
  translator op for op against the host translator at 256 x 64, and the
  16-point fused aged sweep against per-point ``run()``.
* ``pallas`` — the three (max,+) kernels compiled for the chip: the
  ``run_many`` megakernel, the periodic channel kernel and the
  trace-indexed kernel, each against the scan engine.

With ``--chips 4`` only the sharded path runs: ``sweep_tables`` over 64
points and the fused aged FTL sweep, sharded over the 4-chip
``("points",)`` mesh against one device, bit for bit, with every device
holding its shard of the output.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent


def _gate(chips: int):
    """Exit unless JAX sees at least ``chips`` TPU devices."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU chip found (JAX's default device is "
                 f"{devices[0].platform}); nothing runs on the CPU instead")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU chips, "
                 f"JAX sees {len(devices)}")
    return devices


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.abs(b)))


def _timed(fn):
    """(result, first-call seconds, warm seconds); results are blocked
    on before the clock stops."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, cold, time.perf_counter() - t0


def _report(phase: str, **fields) -> None:
    import jax

    print(json.dumps({"phase": phase, "device": jax.devices()[0].device_kind,
                      **fields}), flush=True)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

N_STREAM, CHUNK, N_PREFIX = 1_048_576, 65_536, 16_384
N_SWEEP = 65_536
N_LOAD, LOAD_IA_US = 65_536, 9.0     # ~80% of the 4x16 drive's capacity
FTL_BLOCKS, FTL_PPB, N_OVERWRITE = 4096, 64, 131_072
FTL_SMALL_BLOCKS, N_SMALL = 256, 20_000
N_FLEET, T_FLEET, N_EXACT = 128, 4096, 16
N_PERIODIC, N_INDEXED = 4096, 16_384


def _paper_drive():
    from repro.api import CellType, InterfaceKind, Simulator, SSDConfig
    return Simulator.for_config(SSDConfig(
        interface=InterfaceKind.PROPOSED, cell=CellType.SLC, channels=4,
        ways=16))


def phase_stream(sim) -> None:
    from repro.core.sim_ref import simulate_trace_ref
    from repro.core.trace import mixed_trace, mixed_trace_chunks

    def chunks(n):
        return mixed_trace_chunks(n, 4, 16, read_fraction=0.7,
                                  chunk_len=CHUNK, seed=0)

    res, cold, warm = _timed(lambda: sim.run_stream(chunks(N_STREAM),
                                                    objective="all"))
    trace = mixed_trace(N_STREAM, 4, 16, 0.7, seed=0)
    scan, s_cold, s_warm = _timed(lambda: sim.run(trace, engine="scan",
                                                  objective="all"))
    _check(res.n_ops == N_STREAM, f"streamed {res.n_ops} ops")
    _check(res.end_us == scan.end_us,
           f"stream end {res.end_us!r} != scan {scan.end_us!r}")
    _check(np.array_equal(res.energy.op_sums_uj(), scan.energy.op_sums_uj())
           and res.energy.total_j == scan.energy.total_j,
           f"stream energy {res.energy.op_sums_uj()} != scan "
           f"{scan.energy.op_sums_uj()}")
    prefix = sim.run_stream(chunks(N_PREFIX))
    oracle = simulate_trace_ref(sim.table, mixed_trace(N_PREFIX, 4, 16, 0.7,
                                                       seed=0))
    rel = _rel(prefix.end_us, oracle)
    _check(rel < 1e-3, f"stream prefix vs oracle rel {rel}")
    _report("stream", shapes={"ops": N_STREAM, "chunk": CHUNK,
                              "geometry": "4x16", "state_rows": 74},
            first_call_s=cold, warm_s=warm, scan_first_call_s=s_cold,
            scan_warm_s=s_warm, end_us=res.end_us,
            energy_j=res.energy.total_j, stream_equals_scan=True,
            oracle_prefix_ops=N_PREFIX, oracle_rel=rel,
            ops_per_s_warm=N_STREAM / warm)


def phase_sweep(sim) -> None:
    from repro.api import CellType, InterfaceKind, SSDConfig, op_class_table
    from repro.core.trace import mixed_trace

    tables = [op_class_table(SSDConfig(interface=k, cell=c, channels=4,
                                       ways=w))
              for k in (InterfaceKind.CONV, InterfaceKind.PROPOSED)
              for c in CellType for w in range(1, 17)]
    trace = mixed_trace(N_SWEEP, 4, 16, 0.7, seed=1)
    pre, cold, warm = _timed(lambda: sim.sweep(tables, trace))
    scan, s_cold, s_warm = _timed(lambda: sim.sweep(tables, trace,
                                                    engine="scan"))
    rel = _rel(pre, scan)
    _check(pre.shape == (len(tables),) and rel < 1e-3,
           f"prefix sweep vs scan rel {rel}")
    _report("sweep", shapes={"points": len(tables), "ops": N_SWEEP,
                             "geometry": "4x16"},
            first_call_s=cold, warm_s=warm, scan_first_call_s=s_cold,
            scan_warm_s=s_warm, points_per_s_warm=len(tables) / warm,
            prefix_vs_scan_rel=rel)


def phase_latency(sim) -> None:
    from repro.api import poisson_stream

    load = poisson_stream(N_LOAD, LOAD_IA_US, read_fraction=0.7, seed=2)
    out = {}
    for policy in ("least_loaded", "stripe"):
        res, cold, warm = _timed(lambda: sim.run(load, sched_policy=policy))
        out[policy] = {"first_call_s": cold, "warm_s": warm,
                       "p50_us": res.p50_us, "p99_us": res.p99_us,
                       "end_us": res.end_us}
    pre = sim.run(load, sched_policy="stripe", engine="prefix")
    rel = _rel(out["stripe"]["end_us"], pre.end_us)
    _check(rel < 1e-3, f"stripe scan vs prefix rel {rel}")
    _check(all(np.isfinite(v["p99_us"]) and v["p99_us"] >= v["p50_us"] > 0
               for v in out.values()), f"bad percentiles {out}")
    _report("latency", shapes={"requests": N_LOAD,
                               "mean_interarrival_us": LOAD_IA_US,
                               "geometry": "4x16"},
            stripe_vs_prefix_rel=rel, **out)


def _ftl_specs():
    from repro.api import FTLSpec
    return [FTLSpec(blocks=FTL_BLOCKS, pages_per_block=FTL_PPB,
                    overprovision=float(op), gc_policy="greedy",
                    precondition=True)
            for op in np.linspace(0.12, 0.5, 16)]


def _ftl_step_seconds(spec, stream, fs):
    """Warm seconds per fused translation step: one scan fold over
    ``stream`` from drive state ``fs``, its buffer grown until the
    stream is consumed, timed warm and divided by its step count.  The
    first-call seconds cover the growth loop, compiles included."""
    import jax

    from repro.api import request_lpns
    from repro.core import ftl_scan
    from repro.core.workload import request_ops

    cls, arr, rid, pay = request_ops(stream)
    lpn = request_lpns(stream, spec.logical_pages)
    n = len(cls)
    pad = spec.pages_per_block          # the per-step host window's slack
    host = [np.pad(a, (0, pad)) for a in (cls, arr, pay, rid, lpn)]
    host = [host[0].astype(np.int32), host[1].astype(np.float32),
            host[2].astype(bool), host[3].astype(np.int32),
            host[4].astype(np.int32)]
    args = (*host, np.int32(n), np.int32(spec.gc_free_blocks), False, fs)
    t_max = ftl_scan.estimate_t_max(spec, 0, n)
    t0 = time.perf_counter()
    while True:
        fold = jax.jit(ftl_scan.make_translate_fold(
            spec.blocks, spec.pages_per_block, n + pad, t_max))
        out, _ = fold(*args)
        if int(out.h) >= n and int(out.mode) == ftl_scan.MODE_HOST:
            break
        t_max *= 2
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(fold(*args))
    warm = time.perf_counter() - t0
    return warm / t_max, t_max, cold, warm


def phase_ftl(sim) -> None:
    import dataclasses

    import jax

    from repro.api import (analytic_waf, ftl_translate, ftl_translate_scan,
                           overwrite_stream)
    from repro.core.ftl_scan import scan_state_from_host

    specs = _ftl_specs()
    spec = specs[0]
    load = overwrite_stream(N_OVERWRITE, spec.logical_pages, seed=3)
    res, cold, warm = _timed(lambda: sim.run(load, ftl=spec))
    want = analytic_waf(spec.utilization)
    _check(abs(res.waf - want) / want <= 0.10,
           f"greedy WAF {res.waf} vs analytic {want}")

    # drive state at deployed size, and the per-step translation cost
    aged = ftl_translate_scan(overwrite_stream(1, spec.logical_pages),
                              spec).state
    fs = scan_state_from_host(aged)
    state_bytes = sum(x.nbytes for x in jax.tree.leaves(fs))
    step_s, steps, f_cold, f_warm = _ftl_step_seconds(spec, load, fs)
    _report("ftl_state", shapes={"blocks": FTL_BLOCKS, "ppb": FTL_PPB,
                                 "pages": spec.total_pages,
                                 "host_writes": N_OVERWRITE},
            state_device_bytes=state_bytes, translate_steps=steps,
            translate_first_call_s=f_cold, translate_warm_s=f_warm,
            seconds_per_translate_step=step_s)

    small = dataclasses.replace(spec, blocks=FTL_SMALL_BLOCKS)
    s_load = overwrite_stream(N_SMALL, small.logical_pages,
                              read_fraction=0.2, seed=4)
    host, scan = ftl_translate(s_load, small), ftl_translate_scan(s_load,
                                                                  small)
    for field in ("op_cls", "arrival_us", "payload", "request_id", "gc"):
        _check(np.array_equal(getattr(scan, field), getattr(host, field)),
               f"scan translation {field} differs from the host oracle")
    _check(scan.stats == host.stats, f"{scan.stats} != {host.stats}")

    ends, sw_cold, sw_warm = _timed(lambda: sim.sweep(None, load,
                                                      ftl=specs))
    per_point = {0: res.end_us, 15: sim.run(load, ftl=specs[15]).end_us}
    rel = max(_rel(ends[i], e) for i, e in per_point.items())
    _check(ends.shape == (16,) and rel < 1e-3,
           f"fused FTL sweep vs per-point run rel {rel}")
    _report("ftl", shapes={"blocks": FTL_BLOCKS, "ppb": FTL_PPB,
                           "host_writes": N_OVERWRITE, "sweep_points": 16,
                           "oracle_geometry": f"{FTL_SMALL_BLOCKS}x{FTL_PPB}",
                           "oracle_ops": int(len(host.op_cls))},
            first_call_s=cold, warm_s=warm, waf=res.waf, waf_analytic=want,
            sweep_first_call_s=sw_cold, sweep_warm_s=sw_warm,
            sweep_vs_run_rel=rel,
            sweep_equals_run=all(ends[i] == e for i, e in per_point.items()))


def phase_pallas() -> None:
    import jax

    from repro.api import (CellType, InterfaceKind, Simulator, SSDConfig,
                           lower_static, op_class_table, poisson_stream,
                           sweep_steady_bandwidth_mb_s)
    from repro.core.interface import make_interface
    from repro.core.nand import chip as nand_chip
    from repro.core.sim import page_op_params
    from repro.core.trace import mixed_trace
    from repro.kernels.maxplus.kernel import resolve_interpret
    from repro.kernels.maxplus.ops import (bandwidth_maxplus_mb_s,
                                           run_many_end_time_maxplus)

    _check(resolve_interpret(None) is False, "Pallas would interpret")
    sim = Simulator.for_config(SSDConfig(interface=InterfaceKind.PROPOSED,
                                         cell=CellType.SLC, channels=2,
                                         ways=8))
    fleet = [mixed_trace(T_FLEET, 2, 8, 0.7, seed=100 + i)
             for i in range(N_FLEET)]

    def ends(engine):
        return np.array([r.end_us for r in sim.run_many(fleet,
                                                       engine=engine)])

    many, m_cold, m_warm = _timed(lambda: ends("pallas"))
    scan = ends("scan")
    m_rel = _rel(many, scan)
    _check(m_rel < 1e-3, f"megakernel vs scan rel {m_rel}")
    # the megakernel selects each lane's matrix by a one-hot matmul; the
    # same fold interpreted on the host CPU selects exactly, so equal
    # bits mean the chip's selection is exact too
    sub = fleet[:N_EXACT]
    chip = run_many_end_time_maxplus(sim.table, sub)
    with jax.default_device(jax.devices("cpu")[0]):
        host = run_many_end_time_maxplus(sim.table, sub, interpret=True)
    _check(np.array_equal(chip, host),
           f"one-hot selection inexact: max rel {_rel(chip, host)}")

    ops = [page_op_params(make_interface(k), nand_chip(c), mode, 16)
           for k in InterfaceKind for c in CellType
           for mode in ("read", "write")]
    ways = [16] * len(ops)
    per, p_cold, p_warm = _timed(lambda: bandwidth_maxplus_mb_s(
        ops, ways, n_pages=N_PERIODIC))
    cols = [np.array([getattr(o, f) for o in ops]) for f in (
        "cmd_us", "pre_us", "slot_us", "post_lo_us", "post_hi_us",
        "ctrl_us", "data_bytes")]
    per_scan = np.asarray(sweep_steady_bandwidth_mb_s(
        *cols, np.array(ways), n_pages=N_PERIODIC, engine="scan"))
    p_rel = _rel(per, per_scan)
    _check(p_rel < 1e-3, f"periodic kernel vs scan rel {p_rel}")

    load = poisson_stream(N_INDEXED, 12.0, read_fraction=0.7, seed=6)
    trace = lower_static(load, 2, 8).trace
    tables = [op_class_table(SSDConfig(interface=k, cell=c, channels=2,
                                       ways=8))
              for k in InterfaceKind for c in CellType]
    idx, i_cold, i_warm = _timed(lambda: sim.sweep(tables, trace,
                                                   engine="pallas"))
    i_rel = _rel(idx, sim.sweep(tables, trace, engine="scan"))
    _check(i_rel < 1e-3, f"trace-indexed kernel vs scan rel {i_rel}")
    e_pl = sim.run(trace, engine="pallas", objective="energy").energy
    e_sc = sim.run(trace, engine="scan", objective="energy").energy
    e_rel = _rel(e_pl.op_sums_uj(), e_sc.op_sums_uj())
    _check(e_rel < 1e-3, f"trace-indexed energy vs scan rel {e_rel}")

    # past the VMEM limit the kernel raises; it never falls back
    wide = Simulator.for_config(SSDConfig(channels=4, ways=16))
    try:
        wide.run(mixed_trace(1024, 4, 16, 0.7, seed=7), engine="pallas")
        refused = ""
    except ValueError as e:
        refused = str(e)
    _check("VMEM" in refused, f"4x16 trace-indexed kernel ran: {refused}")
    _report("pallas", shapes={
                "megakernel": {"traces": N_FLEET, "ops": T_FLEET,
                               "geometry": "2x8"},
                "periodic": {"points": len(ops), "pages": N_PERIODIC,
                             "geometry": "1x16"},
                "indexed": {"points": len(tables), "ops": N_INDEXED,
                            "geometry": "2x8"}},
            megakernel_first_call_s=m_cold, megakernel_warm_s=m_warm,
            megakernel_vs_scan_rel=m_rel,
            megakernel_equals_scan=bool(np.array_equal(many, scan)),
            megakernel_equals_host_interpret=N_EXACT,
            periodic_first_call_s=p_cold, periodic_warm_s=p_warm,
            periodic_vs_scan_rel=p_rel,
            indexed_first_call_s=i_cold, indexed_warm_s=i_warm,
            indexed_vs_scan_rel=i_rel, indexed_energy_vs_scan_rel=e_rel,
            refused_beyond_vmem=refused)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def _spy_shards(seen: list):
    """Record every output of the sweeps' shard_map wrapper."""
    import repro.core.api as core_api

    inner = core_api._shard_points

    def spy(mesh, fn, *, n_sharded):
        call = inner(mesh, fn, n_sharded=n_sharded)

        def recorded(*args):
            out = call(*args)
            seen.append(out)
            return out
        return recorded

    core_api._shard_points = spy


def _check_spread(seen: list, devices) -> int:
    """Every leaf of every sharded output is split over all devices."""
    import jax

    n = 0
    for out in seen:
        for leaf in jax.tree.leaves(out):
            held = {s.device for s in leaf.addressable_shards}
            rows = {s.data.shape[0] for s in leaf.addressable_shards}
            _check(held == set(devices) and len(rows) == 1
                   and rows.pop() * len(devices) == leaf.shape[0],
                   f"output {leaf.shape} held by {held}")
            n += 1
    return n


def phase_sharded(devices) -> None:
    from repro.api import (CellType, InterfaceKind, SSDConfig,
                           op_class_table, overwrite_stream, sweep_tables)
    from repro.core.trace import mixed_trace

    seen: list = []
    _spy_shards(seen)
    sim = _paper_drive()
    tables = [op_class_table(SSDConfig(interface=k, cell=c, channels=4,
                                       ways=w))
              for k in (InterfaceKind.CONV, InterfaceKind.PROPOSED)
              for c in CellType for w in range(1, 17)]
    trace = mixed_trace(N_SWEEP, 4, 16, 0.7, seed=1)
    sh, cold, warm = _timed(lambda: sweep_tables(tables, trace, shard=True))
    one, o_cold, o_warm = _timed(lambda: sweep_tables(tables, trace,
                                                      shard=False))
    _check(np.array_equal(sh, one), "sharded sweep_tables != one device")

    specs = _ftl_specs()
    load = overwrite_stream(N_OVERWRITE, specs[0].logical_pages, seed=3)
    t0 = time.perf_counter()
    f_sh = sim.sweep(None, load, ftl=specs, shard=True)
    f_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    f_one = sim.sweep(None, load, ftl=specs, shard=False)
    fo_cold = time.perf_counter() - t0
    _check(np.array_equal(f_sh, f_one), "sharded FTL sweep != one device")
    _check(len(seen) > 0, "no sharded program ran")
    leaves = _check_spread(seen, devices)
    _report("sharded", chips=len(devices),
            shapes={"sweep_points": len(tables), "ops": N_SWEEP,
                    "ftl_points": len(specs), "ftl_blocks": FTL_BLOCKS,
                    "host_writes": N_OVERWRITE},
            sweep_sharded_first_call_s=cold, sweep_sharded_warm_s=warm,
            sweep_one_device_first_call_s=o_cold,
            sweep_one_device_warm_s=o_warm,
            ftl_sharded_first_call_s=f_cold,
            ftl_one_device_first_call_s=fo_cold,
            bit_equal=True, sharded_outputs_checked=leaves)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded sweeps, on four chips")
    args = ap.parse_args()
    devices = _gate(args.chips)

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache

    print(json.dumps({"compile_cache": use_compile_cache()}), flush=True)
    if args.chips == 4:
        phase_sharded(devices[:4])
    else:
        sim = _paper_drive()
        phase_stream(sim)
        phase_sweep(sim)
        phase_latency(sim)
        phase_ftl(sim)
        phase_pallas()
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
