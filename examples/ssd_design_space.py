"""Design-space exploration with the paper's SSD model (paper §5.3.2 +
capacity planning for the storage tier), extended to the mixed
read/write op-trace workloads the paper could not express.

    PYTHONPATH=src python examples/ssd_design_space.py
"""

import time

from repro.api import (Simulator, build_workload, multi_tenant,
                       poisson_stream, bursty_stream, steady_bandwidth_mb_s,
                       sweep_tables)
from repro.core.interface import InterfaceKind
from repro.core.nand import CellType
from repro.core.sim import SSDConfig
from repro.core.trace import checkpoint_trace, datapipe_trace
from repro.storage.kvoffload import KV_SHAPES, plan_kv_offload
from repro.storage.ssd_model import (compare_interfaces,
                                     compare_interfaces_trace, plan_geometry,
                                     plan_geometry_for_trace)


def main():
    print("== constant-capacity channel/way trade-off (paper Table 4, SLC read) ==")
    print("   (all channels simulated jointly against the shared controller)")
    for channels, ways in ((1, 16), (2, 8), (4, 4)):
        row = []
        for kind in InterfaceKind:
            cfg = SSDConfig(interface=kind, cell=CellType.SLC,
                            channels=channels, ways=ways)
            row.append(f"{kind.value}={steady_bandwidth_mb_s(cfg, 'read'):6.1f}")
        print(f"  {channels}ch x {ways:2d}way : " + "  ".join(row) + " MB/s")

    print("\n== mixed-workload design points (beyond paper §5.3: 70/30 r/w) ==")
    print("   (bandwidth + phase-resolved controller energy, DESIGN.md §2.4)")
    bd = None
    for channels, ways in ((1, 16), (2, 8), (4, 4)):
        tr = build_workload("mixed", SSDConfig(channels=channels, ways=ways),
                            read_fraction=0.7, seed=7)
        ests = compare_interfaces_trace(tr, cell=CellType.MLC)
        row = "  ".join(f"{k}={e.bandwidth_mb_s:6.1f}" for k, e in ests.items())
        nj = "  ".join(f"{k}={e.energy.nj_per_byte:5.2f}"
                       for k, e in ests.items())
        print(f"  {channels}ch x {ways:2d}way : {row} MB/s")
        print(f"  {'':14s}  {nj} nJ/B")
        if (channels, ways) == (2, 8):
            bd = ests["proposed"].energy
    print(f"  phase split (proposed, 2ch x 8way): {bd.describe()}")

    print("\n== log-depth engines: 2048-op mixed sweep (DESIGN.md §2.3) ==")
    print("   (one Simulator session per design point; same recurrence,")
    print("    O(segment+log T) depth instead of O(T))")
    cfg = SSDConfig(cell=CellType.MLC, channels=2, ways=8)
    tr2k = build_workload("mixed", cfg, n_ops=2048, read_fraction=0.7, seed=3)
    sims = [Simulator.for_config(SSDConfig(interface=k, cell=c,
                                           channels=2, ways=8))
            for k in InterfaceKind for c in CellType]
    tables = [s.table for s in sims]
    scan_us = [s.run(tr2k).end_us for s in sims]         # compile + run
    px_us = sweep_tables(tables, tr2k, segment_len=128)
    t0 = time.perf_counter()
    scan_us = [s.run(tr2k).end_us for s in sims]
    t_scan = time.perf_counter() - t0
    t0 = time.perf_counter()
    px_us = sweep_tables(tables, tr2k, segment_len=128)
    t_px = time.perf_counter() - t0
    worst = max(abs(a - b) / b for a, b in zip(px_us, scan_us))
    print(f"  scan engine   : {t_scan * 1e3:6.1f} ms for {len(tables)} design points")
    print(f"  prefix engine : {t_px * 1e3:6.1f} ms  (segmented, batched; "
          f"max rel dev {worst:.1e})")

    print("\n== scheduler policy as a design axis (DESIGN.md §2.6) ==")
    print("   (hot/cold-skewed multi-tenant load: a bursty write tenant")
    print("    over a Poisson read trickle; p50/p99 request latency per")
    print("    policy x geometry — dynamic dispatch is the cheap lever")
    print("    when adding ways/channels is not on the table)")
    hot = bursty_stream(100, burst_len=20, gap_us=1500.0,
                        read_fraction=0.1, seed=0, stream=0)
    cold = poisson_stream(100, mean_interarrival_us=80.0,
                          read_fraction=0.9, seed=100, stream=1)
    load = multi_tenant([hot, cold])
    for channels, ways in ((2, 4), (2, 8), (4, 4)):
        sim = Simulator.for_config(
            SSDConfig(cell=CellType.MLC, channels=channels, ways=ways))
        row = []
        for policy in ("stripe", "round_robin", "least_loaded",
                       "earliest_ready"):
            res = sim.run(load, sched_policy=policy)
            row.append(f"{policy}={res.p50_us:5.0f}/{res.p99_us:5.0f}")
        print(f"  {channels}ch x {ways:2d}way : " + "  ".join(row)
              + "  (p50/p99 us)")

    print("\n== queue-depth sweep: closed-loop client, 2ch x 8way MLC ==")
    from repro.api import closed_loop_stream
    sim = Simulator.for_config(SSDConfig(cell=CellType.MLC, channels=2,
                                         ways=8))
    for qd in (1, 2, 4, 8, 16, 32):
        res = sim.run(closed_loop_stream(384, qd, service_us=60.0,
                                         read_fraction=0.7, seed=9),
                      sched_policy="least_loaded")
        print(f"  QD={qd:2d}: p50 {res.p50_us:7.1f} us   "
              f"p99 {res.p99_us:7.1f} us   {res.mb_s:6.1f} MB/s")

    print("\n== aging as a design axis: overprovisioning x GC policy ==")
    print("   (FTL stage, DESIGN.md §2.10: steady-state WAF and the")
    print("    fresh-vs-aged bandwidth cliff; overprovisioning trades")
    print("    usable capacity for sustained write bandwidth, the victim")
    print("    policy trades firmware complexity for WAF under skew)")
    from repro.api import FTLSpec, aging_stream, analytic_waf
    sim = Simulator.for_config(SSDConfig(cell=CellType.MLC, channels=2,
                                         ways=8))
    aged = None
    for op in (0.12, 0.25, 0.5):
        row = []
        for policy in ("greedy", "lru"):
            spec = FTLSpec(blocks=128, pages_per_block=32,
                           overprovision=op, gc_policy=policy,
                           precondition=True)
            aged = sim.run(aging_stream(6000,
                                        int(spec.logical_pages * 0.95),
                                        hot_fraction=0.2, hot_traffic=0.8,
                                        seed=11),
                           ftl=spec)
            row.append(f"{policy}: WAF {aged.waf:4.2f} "
                       f"{aged.mb_s:5.1f} MB/s")
        print(f"  OP {op:4.2f} (uniform analytic WAF "
              f"{analytic_waf(1.0 / (1.0 + op)):4.2f}) : " + "   ".join(row))
    print(f"  fresh-drive reference (OP 0.50): {aged.fresh_mb_s:5.1f} MB/s"
          f" -> the cliff is {aged.mb_s / aged.fresh_mb_s:4.2f}x")

    print("\n== fused aged sweep: 12 overprovisioning points, one closure ==")
    print("   (compiled scan translator, DESIGN.md §2.11: translate ->")
    print("    lower -> simulate rides vmap; preconditioned states and")
    print("    buffer sizes are memoised, so the warm sweep skips the")
    print("    aging ramp the per-point path re-pays on every call)")
    import numpy as np
    from repro.api import overwrite_stream
    specs = [FTLSpec(blocks=128, pages_per_block=32,
                     overprovision=float(op), precondition=True)
             for op in np.linspace(0.12, 0.5, 12)]
    mixed = overwrite_stream(4000, specs[-1].logical_pages,
                             read_fraction=0.5, seed=7)
    t0 = time.perf_counter()
    ends = sim.sweep(None, mixed, ftl=specs)          # compile + age
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    ends = sim.sweep(None, mixed, ftl=specs)
    t_warm = time.perf_counter() - t0
    span = ", ".join(f"{e / 1e3:.1f}" for e in
                     (ends[0], ends[len(ends) // 2], ends[-1]))
    print(f"  12-point 50/50 aged sweep: cold {t_cold:5.2f}s, "
          f"warm {t_warm * 1e3:6.1f} ms")
    print(f"  end times OP 0.12 / 0.29 / 0.50: {span} ms "
          f"(more spare blocks -> less GC -> earlier finish)")

    print("\n== checkpoint-stall planning: 2.7B params (minicpm), bf16+opt ==")
    print("   (MLC tier first; fall back to an SLC tier when contention-")
    print("    limited MLC writes cannot meet the stall budget)")
    nbytes = int(2.7e9 * 2 * 3)
    for budget in (150.0, 95.0, 30.0):
        plan = None
        for cell in (CellType.MLC, CellType.SLC):
            plan = plan_geometry_for_trace(
                lambda cfg: checkpoint_trace(nbytes, cfg),
                budget_s=budget, cell=cell, total_bytes=nbytes)
            if plan:
                break
        print(f"  budget {budget:5.1f}s -> "
              + (plan.describe() if plan else "no geometry fits"))

    print("\n== dataloader refill: 10 GiB, trace-planned vs byte-planned ==")
    ten_gib = 10 << 30
    t_plan = plan_geometry_for_trace(
        lambda cfg: datapipe_trace(ten_gib, cfg, hedge_fraction=0.05),
        budget_s=60.0, total_bytes=ten_gib)
    b_plan = plan_geometry(ten_gib, budget_s=60.0, mode="read")
    e_plan = plan_geometry_for_trace(
        lambda cfg: datapipe_trace(ten_gib, cfg, hedge_fraction=0.05),
        budget_s=60.0, total_bytes=ten_gib, objective="energy")
    print("  trace (5% hedged):", t_plan.describe() if t_plan else "none")
    print("  bytes (pure read):", b_plan.describe() if b_plan else "none")
    print("  min-energy fit   :", e_plan.describe() if e_plan else "none")
    for name, est in compare_interfaces(ten_gib, "read").items():
        print(f"  {name:10s}: {est.seconds:6.1f} s  {est.energy_joules*1e3:7.1f} mJ")

    print("\n== KV offload feasibility at 524288-token decode ==")
    for arch_id in ("qwen2-0.5b", "recurrentgemma-9b", "xlstm-350m"):
        plan = plan_kv_offload(KV_SHAPES[arch_id], 524288)
        print(f"  {plan.note}")


if __name__ == "__main__":
    main()
