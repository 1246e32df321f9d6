"""``repro.api`` — the unified simulation surface (re-export of
``repro.core.api`` plus the types a query needs).

Quickstart::

    from repro.api import Simulator, SSDConfig, build_workload

    cfg = SSDConfig(channels=4, ways=8)
    sim = Simulator.for_config(cfg)             # shared, jit-cached session
    res = sim.run(build_workload("mixed", cfg, read_fraction=0.7),
                  objective="all")
    print(res.describe(), res.energy.nj_per_byte)

Latency under load (request-level workloads, DESIGN.md §2.6)::

    from repro.api import poisson_stream

    load = poisson_stream(512, mean_interarrival_us=40.0, seed=0)
    res = sim.run(load, sched_policy="least_loaded")   # dynamic dispatch
    print(res.p50_us, res.p99_us)

Reliability and tail latency (DESIGN.md §2.8)::

    from repro.api import FaultSpec

    worn = FaultSpec(wear=0.8, hedge_fraction=0.3, seed=7)
    res = sim.run(load, faults=worn)            # retries, remaps, hedges
    print(res.p99_9_us, res.n_remap_ops, res.retry_hist)

Aging and garbage collection (the FTL stage, DESIGN.md §2.10)::

    from repro.api import FTLSpec, overwrite_stream

    aged = sim.run(overwrite_stream(4096, footprint_pages=2048),
                   ftl=FTLSpec(overprovision=0.25, precondition=True))
    print(aged.waf, aged.mb_s, aged.fresh_mb_s)    # steady vs fresh

Aged design-space sweeps ride the compiled translation engine
(DESIGN.md §2.11) — one fused translate→lower→simulate fold per point,
vmapped across points (sharded over devices when there are several)::

    import dataclasses

    points = [FTLSpec(overprovision=op, gc_policy=g, precondition=True)
              for op in (0.12, 0.25, 0.5) for g in ("greedy", "lru")]
    ends = sim.sweep(None, overwrite_stream(4096, 2048), ftl=points)

See DESIGN.md §2.5 for the request/response model, the engine registry
and the cache keying; §2.6 for workloads and scheduling policies; §2.8
for the fault model and its determinism contract; §2.10 for the FTL
translation stage, WAF accounting and the GC policy registry; §2.11
for the compiled (lax.scan) translation engine behind the fault-free
default path, the fused sweep and the streaming chunked variant.
"""

from repro.core.api import (CacheInfo, CapabilityError, Engine, EngineCaps,
                            OBJECTIVES, Objective, Policy, SimRequest,
                            SimResult, Simulator, engine_capabilities,
                            get_engine, register_engine, registered_engines,
                            simulator_for, steady_bandwidth_mb_s,
                            steady_channel_bandwidth_mb_s,
                            sweep_steady_bandwidth_mb_s, sweep_tables)
from repro.core.energy import EnergyBreakdown
from repro.core.faults import FaultSampler, FaultSpec
from repro.core.ftl import (FTLSpec, FTLStats, FTLTranslation, FTL_LABELS,
                            GC_POLICIES, analytic_waf, ftl_op_class_table,
                            precondition_lpns, select_victim)
from repro.core.ftl import translate as ftl_translate
from repro.core.ftl_scan import translate_scan as ftl_translate_scan
from repro.core.interface import InterfaceKind
from repro.core.nand import CellType
from repro.core.sched import (DYNAMIC_POLICIES, LoweredWorkload,
                              SCHED_POLICIES, STATIC_POLICIES, apply_faults,
                              lower_ops, lower_ops_chunk, lower_static,
                              policy_is_dynamic)
from repro.core.sim import PageOpParams, SSDConfig
from repro.core.trace import (OpClassTable, OpTrace, READ, WRITE,
                              op_class_table, workload_trace)
from repro.core.workload import (RequestStream, aging_stream, build_workload,
                                 bursty_stream, checkpoint_requests,
                                 closed_loop_stream, datapipe_requests,
                                 iter_request_chunks, jesd219_stream,
                                 kvoffload_requests, multi_tenant,
                                 overwrite_stream, poisson_stream,
                                 request_lpns, request_partial, with_hedges)

__all__ = [
    # the session API proper
    "CacheInfo", "CapabilityError", "Engine", "EngineCaps", "OBJECTIVES",
    "Objective", "Policy", "SimRequest", "SimResult", "Simulator",
    "engine_capabilities", "get_engine", "register_engine",
    "registered_engines", "simulator_for", "steady_bandwidth_mb_s",
    "steady_channel_bandwidth_mb_s", "sweep_steady_bandwidth_mb_s",
    "sweep_tables",
    # the request-level workload + scheduler layer (DESIGN.md §2.6)
    "DYNAMIC_POLICIES", "LoweredWorkload", "RequestStream",
    "SCHED_POLICIES", "STATIC_POLICIES", "build_workload", "bursty_stream",
    "checkpoint_requests", "closed_loop_stream", "datapipe_requests",
    "iter_request_chunks", "kvoffload_requests", "lower_static",
    "multi_tenant", "policy_is_dynamic", "poisson_stream", "aging_stream",
    "overwrite_stream", "request_lpns", "jesd219_stream", "request_partial",
    # the reliability layer (DESIGN.md §2.8)
    "FaultSampler", "FaultSpec", "apply_faults", "with_hedges",
    # the FTL stage (DESIGN.md §2.10-§2.11)
    "FTLSpec", "FTLStats", "FTLTranslation", "FTL_LABELS", "GC_POLICIES",
    "analytic_waf", "ftl_op_class_table", "ftl_translate",
    "ftl_translate_scan", "lower_ops", "lower_ops_chunk",
    "precondition_lpns", "select_victim",
    # the types a request/result is made of
    "CellType", "EnergyBreakdown", "InterfaceKind", "OpClassTable",
    "OpTrace", "PageOpParams", "READ", "SSDConfig", "WRITE",
    "op_class_table", "workload_trace",
]
