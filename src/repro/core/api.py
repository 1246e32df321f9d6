"""Unified ``Simulator`` session API: one request/response surface over
every simulation engine (DESIGN.md §2.5).

After the engine work of PRs 1-3 the query surface had fragmented into
seven entry points with incompatible knobs (``engine=`` on
``trace.simulate``, ``strategy=`` on the Pallas ops, ``combine=`` on the
prefix folds, ``engine="squaring"`` only on the homogeneous sweeps) and
incompatible result types (bare floats, arrays, ``EnergyBreakdown``,
``IOEstimate``).  This module absorbs that dispatch into three pieces:

* an **engine registry** — every evaluation strategy registers once
  under a name (``scan`` / ``prefix`` / ``squaring`` / ``pallas`` /
  ``oracle``) with a declared :class:`EngineCaps` capability row
  (heterogeneous traces?  batched design-point tables?  energy?
  jit-able?).  Unknown names raise one ``ValueError`` listing the
  registered engines; a registered engine asked for something outside
  its capability row raises :class:`CapabilityError` (a ``ValueError``)
  naming the engines that can serve it.  This is the FMMU argument
  (Woo & Min 2017) in software: a uniform request interface in front of
  heterogeneous engines is what makes the pool schedulable.

* a **session object** — :class:`Simulator` binds an ``SSDConfig`` /
  ``OpClassTable`` once, converts the timing table to device arrays
  once, and caches jitted engine closures keyed on
  ``(engine, table geometry, trace-length bucket, policy, ...)`` so
  repeated queries never re-trace or re-convert.  The scan engine runs
  through a masked fold padded to power-of-two length buckets —
  identical results (masked ops are bitwise no-ops on the carried
  state), one compile per bucket instead of one per trace length.
  :meth:`Simulator.run_many` packs heterogeneous traces into those
  buckets and evaluates each bucket group in a single vmapped call —
  the serving path for sweep traffic.  ``Simulator.for_config`` memoises
  sessions per design point so the storage tier and the planners share
  compiled closures process-wide.

* one **request/response pair** — :class:`SimRequest` (trace, policy,
  objective ∈ {end_time, bandwidth, energy, all}, optional engine
  override) in, :class:`SimResult` (end_us, per-channel bus occupancy,
  MB/s, optional ``EnergyBreakdown``) out, for every engine and every
  entry point.  The ``Policy`` literal is validated once, here, in the
  request layer — a typo like ``"bathced"`` raises instead of silently
  simulating ``"eager"``.

Above the trace layer, requests are first-class (DESIGN.md §2.6): a
``SimRequest`` may carry a placement-free
``repro.core.workload.RequestStream`` plus a ``sched_policy`` — static
policies lower offline through ``repro.core.sched`` and reach every
engine; dynamic policies require the ``dispatch`` capability and run
the joint dispatch+simulate fold, attaching per-request latency
percentiles (``SimResult.p50_us`` / ``p99_us``) to the answer.

The legacy functions (``trace.simulate[_batch]``, ``simulate_energy``,
``trace_bandwidth_mb_s``, ``sim.channel_bandwidth_mb_s`` /
``sweep_bandwidth_mb_s`` / ``ssd_bandwidth_mb_s``,
``trace.workload_trace``) survive as thin
shims that emit ``DeprecationWarning`` and delegate here; a
``filterwarnings = error::DeprecationWarning:repro\\.`` rule in
pytest.ini (and the same programmatic filter in ``benchmarks/run_all``)
turns shim calls *from repro-internal modules* into errors, so internal
code can never call its own deprecated surface.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
import warnings
from typing import Literal, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ftl as _ftl
from repro.core import ftl_scan as _ftl_scan
from repro.core import sched as _sched
from repro.core import sim as _sim
from repro.core import spans as _spans
from repro.core import trace as _trace
from repro.core import workload as _workload
from repro.core.energy import (EnergyBreakdown, breakdown_from_sums,
                               op_phase_energy_uj)
from repro.core.faults import FaultSampler, FaultSpec
from repro.core.interface import InterfaceKind
from repro.core.nand import chip as _nand_chip
from repro.core.sched import LoweredWorkload
from repro.core.sim import (MAX_WAYS, PageOpParams, Policy, SSDConfig,
                            policy_is_batched)
from repro.core.trace import OpClassTable, OpTrace, op_class_table
from repro.core.workload import RequestStream, request_ops

Objective = Literal["end_time", "bandwidth", "energy", "all"]
OBJECTIVES: tuple[str, ...] = ("end_time", "bandwidth", "energy", "all")

#: Op-class table columns, in the positional order the jitted engines take.
_TABLE_FIELDS = ("cmd_us", "pre_us", "slot_us", "post_lo_us", "post_hi_us",
                 "ctrl_us", "arb_us")


class CapabilityError(ValueError):
    """A *registered* engine was asked for a query outside its declared
    capability row (vs plain ``ValueError`` for unknown engine names)."""


# ---------------------------------------------------------------------------
# Engine protocol + registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EngineCaps:
    """Declared capability row of one registered engine."""

    name: str
    heterogeneous: bool   # arbitrary OpTrace (vs homogeneous periodic only)
    batched_tables: bool  # one trace x stacked design-point tables
    energy: bool          # phase-resolved energy accumulation
    jittable: bool        # pure-jax: Simulator caches jitted closures
    arrivals: bool = False  # arrival-aware traces (request workloads)
    dispatch: bool = False  # joint dispatch+simulate (dynamic sched policies)
    ftl: bool = False       # FTL-translated streams (GC/erase op classes)

    def describe(self) -> str:
        flags = [k for k in ("heterogeneous", "batched_tables", "energy",
                             "jittable", "arrivals", "dispatch", "ftl")
                 if getattr(self, k)]
        return f"{self.name}: {', '.join(flags) or 'none'}"


@runtime_checkable
class Engine(Protocol):
    """What a registered engine must answer.  ``sim`` is the session —
    it supplies the bound table, device-array conversions and the
    jit-closure cache; engines that declare ``jittable`` use it to keep
    repeated queries compile-free.  Optional capabilities
    (``end_time_batch``, ``steady_channel_end``, ``sweep_steady``) raise
    :class:`CapabilityError` on the base class.  Every engine must also
    answer ``canonical_folds`` — the traceable canonical-request hook
    the ``repro.analysis`` jaxpr layer enforces engine contracts through
    (DESIGN.md §2.9)."""

    caps: EngineCaps

    def end_time(self, sim: "Simulator", trace: OpTrace, *, batched: bool,
                 segment_len: int | None) -> float: ...

    def energy_sums(self, sim: "Simulator", trace: OpTrace,
                    kind: InterfaceKind, *, batched: bool,
                    segment_len: int | None) -> tuple[float, np.ndarray]: ...


_REGISTRY: dict[str, Engine] = {}


def register_engine(name: str, *, heterogeneous: bool, batched_tables: bool,
                    energy: bool, jittable: bool, arrivals: bool = False,
                    dispatch: bool = False, ftl: bool = False):
    """Class decorator: instantiate and register an engine under ``name``
    with its declared capability row.  Names are unique."""

    def deco(cls):
        if name in _REGISTRY:
            raise ValueError(f"engine {name!r} is already registered")
        inst = cls()
        inst.caps = EngineCaps(name=name, heterogeneous=heterogeneous,
                               batched_tables=batched_tables, energy=energy,
                               jittable=jittable, arrivals=arrivals,
                               dispatch=dispatch, ftl=ftl)
        _REGISTRY[name] = inst
        return cls

    return deco


def registered_engines() -> tuple[str, ...]:
    """Sorted names of every registered engine."""
    return tuple(sorted(_REGISTRY))


def engine_capabilities() -> dict[str, EngineCaps]:
    """The full declared capability table, by engine name."""
    return {name: _REGISTRY[name].caps for name in registered_engines()}


def get_engine(name: str) -> Engine:
    """Look up a registered engine; unknown names raise the one shared
    ``ValueError`` every entry point now emits."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown engine {name!r} (registered engines: "
            f"{', '.join(registered_engines())})") from None


def _policy_name(batched: bool) -> str:
    return "batched" if batched else "eager"


def _bucket_len(n: int, floor: int = 64) -> int:
    """Trace lengths round up to power-of-two buckets so jitted closures
    (and compiles) are shared across nearby lengths."""
    return max(floor, 1 << max(0, (n - 1).bit_length()))


def _payload_latencies(lowered: LoweredWorkload, completion_us,
                       stream: RequestStream) -> np.ndarray:
    """Per-request latencies restricted to *payload* requests: hedged
    duplicates are transport, not requests — a duplicate queueing
    behind its primary must not inflate the reported tail.  When the
    stream links duplicates to their primaries (``hedge_of``, the
    ``with_hedges`` builder), the first response wins: the primary is
    credited with ``min(own done, duplicate done)`` — the whole point
    of hedging (DESIGN.md §2.8).  Unlinked legacy duplicates keep the
    conservative bound (the primary's own completion)."""
    comp = np.asarray(completion_us, np.float64)
    done = np.zeros(len(lowered.request_arrival_us), np.float64)
    np.maximum.at(done, lowered.request_id, comp)
    if stream.hedge_of is not None:
        h = np.asarray(stream.hedge_of, np.int64)
        link = h >= 0
        if link.any():
            np.minimum.at(done, h[link], done[link])
    lat = done - np.asarray(lowered.request_arrival_us, np.float64)
    pay = stream.payload_mask()
    return lat if pay.all() else lat[pay]


def _op_arrivals(trace: OpTrace) -> np.ndarray:
    """Per-op arrival array for the engines (zeros = back-to-back)."""
    if trace.arrival_us is None:
        return np.zeros(trace.n_ops, np.float32)
    return np.asarray(trace.arrival_us, np.float32)


def _op_extras(trace: OpTrace) -> np.ndarray:
    """Per-op reliability surcharge array (zeros = fault-free)."""
    if trace.extra_us is None:
        return np.zeros(trace.n_ops, np.float32)
    return np.asarray(trace.extra_us, np.float32)


def _trace_args(trace: OpTrace):
    return (jnp.asarray(trace.cls), jnp.asarray(trace.channel),
            jnp.asarray(trace.way), jnp.asarray(trace.parity),
            jnp.asarray(_op_arrivals(trace)),
            jnp.asarray(_op_extras(trace)))


def _pad_trace_np(trace: OpTrace, t_bucket: int):
    """Zero-pad the per-op arrays to ``t_bucket`` plus the validity mask
    consumed by the masked scan folds (padding ops are state no-ops) —
    the one padding contract ``run`` and ``run_many`` share."""
    pad = t_bucket - trace.n_ops
    valid = np.zeros(t_bucket, bool)
    valid[: trace.n_ops] = True
    return (np.pad(np.asarray(trace.cls), (0, pad)),
            np.pad(np.asarray(trace.channel), (0, pad)),
            np.pad(np.asarray(trace.way), (0, pad)),
            np.pad(np.asarray(trace.parity), (0, pad)),
            np.pad(_op_arrivals(trace), (0, pad)),
            np.pad(_op_extras(trace), (0, pad)),
            valid)


def _padded_trace_args(trace: OpTrace, t_bucket: int):
    return tuple(jnp.asarray(x) for x in _pad_trace_np(trace, t_bucket))


def _steady_channel_args(op: PageOpParams, ways, n_pages: int):
    """(table columns, cls zeros, way, parity, arrival zeros, extra
    zeros) of a single-channel round-robin stream over one op class —
    shared by every engine with the homogeneous-pattern capability."""
    scalars = _op_scalars(op)
    way, parity = _sim._steady_pattern(n_pages, jnp.asarray(ways, jnp.int32))
    zeros = jnp.zeros((n_pages,), jnp.int32)
    zeros_f = jnp.zeros((n_pages,), jnp.float32)
    table = tuple(x[None] for x in scalars) + (jnp.zeros((1,), jnp.float32),)
    return table, zeros, way, parity, zeros_f, zeros_f


def _stacked_table_args(tables: list[OpClassTable]):
    return tuple(jnp.asarray(np.stack([getattr(t, f) for t in tables]))
                 for f in _TABLE_FIELDS)


def _staged_batch_args(tables: list[OpClassTable], trace: OpTrace):
    """The stacked tables and the trace as device arguments of a
    table-batched fold, under the ``ssd.stage`` span."""
    with _spans.span("stage", steps=trace.n_ops):
        return _stacked_table_args(tables) + _trace_args(trace)


def _canonical_trace(arrivals: bool = True) -> OpTrace:
    """The canonical small request the analysis layer traces every
    engine on (DESIGN.md §2.9): a fixed-seed mixed read/write trace on
    2 channels x 4 ways.  With ``arrivals=True`` it carries nonzero
    per-op arrivals *and* reliability surcharges, so the origin row and
    the ``extra_us`` side-channel are part of the traced fold and the
    dtype/RNG invariants cover them."""
    t = _trace.mixed_trace(48, 2, 4, read_fraction=0.5, seed=7)
    if not arrivals:
        return t
    n = t.n_ops
    return dataclasses.replace(
        t,
        arrival_us=np.linspace(0.0, 40.0, n, dtype=np.float32),
        extra_us=np.where(np.arange(n) % 7 == 0, 3.0, 0.0
                          ).astype(np.float32))


class _EngineBase:
    """Shared defaults: optional capabilities raise ``CapabilityError``
    naming the registered engines that *do* implement them (derived
    from the registry, so new engines appear automatically)."""

    caps: EngineCaps

    def _unsupported(self, what: str, method: str):
        base = getattr(_EngineBase, method)
        supported = sorted(
            name for name, eng in _REGISTRY.items()
            if getattr(type(eng), method, base) is not base)
        raise CapabilityError(
            f"engine {self.caps.name!r} does not support {what} "
            f"(engines that do: {', '.join(supported)})")

    def end_time_batch(self, tables, trace, *, batched, segment_len,
                       combine="chain") -> np.ndarray:
        self._unsupported("batched design-point tables", "end_time_batch")

    def steady_channel_end(self, op: PageOpParams, ways, *, n_pages: int,
                           batched: bool):
        self._unsupported("homogeneous single-channel patterns",
                          "steady_channel_end")

    def sweep_steady(self, scalars, data_bytes, ways, *, n_pages: int,
                     batched: bool):
        self._unsupported("homogeneous design-point sweeps", "sweep_steady")

    def completions(self, sim: "Simulator", trace: OpTrace, *,
                    batched: bool,
                    segment_len: int | None = None
                    ) -> tuple[float, np.ndarray]:
        """(end_us, [T] per-op completion times) — what request-latency
        percentiles are computed from.  ``segment_len`` is the chunk
        length for chunked engines (others ignore it)."""
        self._unsupported("per-op completion times", "completions")

    def dispatch_run(self, sim: "Simulator", cls, arrival_us, *,
                     n_channels: int, n_ways: int, rule: str,
                     extra_us=None, retired=None):
        """Joint dispatch+simulate under a dynamic sched policy; returns
        (end_us, completion[T], channel[T], way[T], parity[T]).
        ``extra_us`` / ``retired`` are the reliability-layer inputs:
        per-op surcharges and the bad-block mask the dispatch rule must
        never place an op on (DESIGN.md §2.8)."""
        self._unsupported("dynamic dispatch policies", "dispatch_run")

    def canonical_folds(self, sim: "Simulator"):
        """label -> (fn, args): jax-traceable closures evaluating this
        engine's folds on the canonical request — the hook behind the
        ``repro.analysis`` jaxpr invariant layer (DESIGN.md §2.9), which
        statically asserts per fold: no RNG primitives, f32 dtype
        stability, and a primitive-count budget against the committed
        baseline.  Pure host-Python engines return ``None`` (the AST
        layer still lints their source).  Every registered engine MUST
        override this — the analyzer fails loudly on engines that
        don't, so a new engine cannot land outside the contract net."""
        raise NotImplementedError(
            f"engine {self.caps.name!r} exposes no canonical fold hook "
            "(repro.analysis traces every registered engine; override "
            "canonical_folds, returning None only for host-Python "
            "engines)")


@register_engine("scan", heterogeneous=True, batched_tables=True,
                 energy=True, jittable=True, arrivals=True, dispatch=True,
                 ftl=True)
class ScanEngine(_EngineBase):
    """O(T) ``lax.scan`` fold (DESIGN.md §2.2) — the default engine.
    Session queries run the masked fold padded to length buckets, so
    repeated nearby-length queries share one compile.  On a TPU the
    masked fold runs as one Pallas kernel (``repro.kernels.scanfold``,
    bit-identical) that stops at the true op count; a ``lax.scan`` pays
    ~20 µs of loop overhead a step there, while XLA:CPU runs it fast."""

    def _masked_fold(self, sim, trace, *, batched, completions, steps=None):
        """(end_us, per-op completions or None) of the padded bucket: the
        bucket of ``trace.n_ops``, or of ``steps`` where that is more."""
        from repro.kernels.maxplus.kernel import resolve_interpret
        from repro.kernels.scanfold import ops as _scanfold

        t_b = _bucket_len(max(trace.n_ops, steps or 0))
        kernel = not resolve_interpret(None)
        if kernel:
            fold = (_scanfold.scan_fold_completions if completions
                    else _scanfold.scan_fold_end_time)
        else:
            fold = (_sim.trace_completions_masked if completions
                    else _sim.trace_end_time_masked)
        fn = sim._closure(
            ("scan-completions" if completions else "scan", trace.channels,
             t_b, batched, kernel),
            lambda: functools.partial(
                fold, *sim._targs, n_channels=trace.channels,
                batched=batched))
        with _spans.span("stage", steps=t_b):
            padded = _pad_trace_np(trace, t_b)
            # the kernel takes the op count where the scan takes the mask
            tail = (np.int32(trace.n_ops),) if kernel else padded[-1:]
            args = tuple(jnp.asarray(x) for x in padded[:-1] + tail)
        with _spans.span("fold", engine="scan", ops=trace.n_ops,
                         steps=trace.n_ops if kernel else t_b, points=1,
                         kernel=int(kernel)):
            if not completions:
                return float(fn(*args)), None
            end, comp = fn(*args)
            return float(end), np.asarray(comp, np.float64)[: trace.n_ops]

    def end_time(self, sim, trace, *, batched, segment_len, steps=None):
        return self._masked_fold(sim, trace, batched=batched,
                                 completions=False, steps=steps)[0]

    def completions(self, sim, trace, *, batched, segment_len=None,
                    steps=None):
        return self._masked_fold(sim, trace, batched=batched,
                                 completions=True, steps=steps)

    def dispatch_run(self, sim, cls, arrival_us, *, n_channels, n_ways,
                     rule, extra_us=None, retired=None):
        fn = sim._closure(
            ("scan-dispatch", n_channels, n_ways, len(cls), rule,
             extra_us is not None, retired is not None),
            lambda: functools.partial(
                _sim.dispatch_trace, *sim._targs,
                n_channels=n_channels, n_ways=n_ways, rule=rule))
        n = len(cls)
        with _spans.span("stage", steps=n):
            kw = {}
            if extra_us is not None:
                kw["extra_us"] = jnp.asarray(extra_us, jnp.float32)
            if retired is not None:
                kw["retired"] = jnp.asarray(retired, bool)
            args = (jnp.asarray(cls, jnp.int32),
                    jnp.asarray(arrival_us, jnp.float32))
        with _spans.span("fold", engine="scan", ops=n, steps=n, points=1):
            end, comp, chan, way, par = fn(*args, **kw)
            return (float(end), np.asarray(comp, np.float64),
                    np.asarray(chan), np.asarray(way), np.asarray(par))

    def energy_sums(self, sim, trace, kind, *, batched, segment_len):
        fn = sim._closure(
            ("scan-energy", trace.channels, trace.n_ops, batched, kind),
            lambda: functools.partial(
                _sim.trace_end_time_energy, *sim._targs,
                sim._energy_table(kind),
                n_channels=trace.channels, batched=batched))
        end, sums = fn(*_trace_args(trace))
        return float(end), np.asarray(sums, np.float64)

    def end_time_batch(self, tables, trace, *, batched, segment_len,
                       combine="chain"):
        args = _staged_batch_args(tables, trace)
        with _spans.span("fold", engine="scan", ops=trace.n_ops,
                         steps=trace.n_ops, points=len(tables)):
            return np.asarray(_sim.trace_end_time_batch(
                *args, n_channels=trace.channels, batched=batched))

    def steady_channel_end(self, op, ways, *, n_pages, batched):
        table, zeros, way, parity, arr, ext = _steady_channel_args(
            op, ways, n_pages)
        return _sim.trace_end_time(
            *table, zeros, zeros, way, parity, arr, ext,
            n_channels=1, batched=batched)

    def sweep_steady(self, scalars, data_bytes, ways, *, n_pages, batched):
        return _sim._sweep_scan_jit(*scalars, data_bytes, ways,
                                    n_pages=n_pages, batched=batched)

    def canonical_folds(self, sim):
        t = _canonical_trace()
        end = functools.partial(
            _sim.trace_end_time_masked, *sim._targs,
            n_channels=t.channels, batched=False)
        disp = functools.partial(
            _sim.dispatch_trace, *sim._targs, n_channels=t.channels,
            n_ways=t.ways, rule="least_loaded")
        folds = {
            "end_time": (end, _padded_trace_args(t, _bucket_len(t.n_ops))),
            "dispatch": (disp, (jnp.asarray(t.cls, jnp.int32),
                                jnp.asarray(_op_arrivals(t)))),
        }
        if sim.config is not None:
            # the FTL stage (DESIGN.md §2.10) reuses this same fold over
            # the extended 7-class table: trace a small deterministic
            # GC-injected stream so the invariant net covers it
            spec = _ftl.FTLSpec(blocks=8, pages_per_block=8,
                                overprovision=0.5)
            ftab = _ftl.ftl_op_class_table(sim.config, spec)
            ftargs = tuple(jnp.asarray(getattr(ftab, f))
                           for f in _TABLE_FIELDS)
            tr = _ftl.translate(
                _workload.overwrite_stream(48, 24, seed=3), spec)
            ft = _sched.lower_ops(tr.op_cls, tr.arrival_us,
                                  sim.config.channels, sim.config.ways,
                                  payload=tr.payload)
            fend = functools.partial(
                _sim.trace_end_time_masked, *ftargs,
                n_channels=ft.channels, batched=False)
            folds["ftl_end_time"] = (
                fend, _padded_trace_args(ft, _bucket_len(ft.n_ops)))
            # the compiled translation engine itself (DESIGN.md §2.11):
            # trace the scan FTL fold over the same small stream, so
            # the invariant net (RNG-free, f32, primitive budget) gates
            # the machine that now feeds every fault-free FTL query
            st = _workload.overwrite_stream(48, 24, seed=3)
            cls, arr, rid, pay = _workload.request_ops(st)
            lpns = _workload.request_lpns(st, spec.logical_pages)
            n_b = 64
            pad = n_b - len(cls)
            tfold = _ftl_scan.make_translate_fold(
                spec.blocks, spec.pages_per_block, n_b, 256)
            folds["ftl_translate"] = (tfold, (
                jnp.asarray(np.pad(cls, (0, pad)), jnp.int32),
                jnp.asarray(np.pad(arr, (0, pad)), jnp.float32),
                jnp.asarray(np.pad(pay, (0, pad)), bool),
                jnp.asarray(np.pad(rid, (0, pad)), jnp.int32),
                jnp.asarray(np.pad(lpns, (0, pad)), jnp.int32),
                jnp.int32(len(cls)), jnp.int32(spec.gc_free_blocks),
                jnp.asarray(False),
                _ftl_scan.scan_state_fresh(spec)))
        return folds


@register_engine("prefix", heterogeneous=True, batched_tables=True,
                 energy=True, jittable=True, arrivals=True, ftl=True)
class PrefixEngine(_EngineBase):
    """Segmented parallel-prefix (max,+) fold, O(L + log T) depth
    (DESIGN.md §2.3); energy rides the same chunking as segment sums."""

    def end_time(self, sim, trace, *, batched, segment_len):
        fn = sim._closure(
            ("prefix", trace.channels, trace.ways, trace.n_ops, batched,
             segment_len),
            lambda: functools.partial(
                _sim.trace_end_time_prefix, *sim._targs,
                n_channels=trace.channels, n_ways=trace.ways,
                batched=batched, segment_len=segment_len))
        return float(fn(*_trace_args(trace)))

    def energy_sums(self, sim, trace, kind, *, batched, segment_len):
        fn = sim._closure(
            ("prefix-energy", trace.channels, trace.ways, trace.n_ops,
             batched, segment_len, kind),
            lambda: functools.partial(
                _sim.trace_end_time_prefix_energy, *sim._targs,
                sim._energy_table(kind),
                n_channels=trace.channels, n_ways=trace.ways,
                batched=batched, segment_len=segment_len))
        end, sums = fn(*_trace_args(trace))
        return float(end), np.asarray(sums, np.float64)

    def end_time_batch(self, tables, trace, *, batched, segment_len,
                       combine="chain"):
        args = _staged_batch_args(tables, trace)
        with _spans.span("fold", engine="prefix", ops=trace.n_ops,
                         steps=trace.n_ops, points=len(tables)):
            return np.asarray(_sim.trace_end_time_prefix_batch(
                *args, n_channels=trace.channels, n_ways=trace.ways,
                batched=batched, segment_len=segment_len,
                combine=combine))

    def steady_channel_end(self, op, ways, *, n_pages, batched):
        table, zeros, way, parity, arr, ext = _steady_channel_args(
            op, ways, n_pages)
        return _sim.trace_end_time_prefix(
            *table, zeros, zeros, way, parity, arr, ext,
            n_channels=1, n_ways=MAX_WAYS, batched=batched)

    def canonical_folds(self, sim):
        t = _canonical_trace()
        fn = functools.partial(
            _sim.trace_end_time_prefix, *sim._targs,
            n_channels=t.channels, n_ways=t.ways, batched=False,
            segment_len=16)
        return {"end_time": (fn, _trace_args(t))}


@register_engine("squaring", heterogeneous=False, batched_tables=False,
                 energy=True, jittable=True)
class SquaringEngine(_EngineBase):
    """Periodic (max,+) matrix squaring, O(log T) matmuls (DESIGN.md
    §2.3).  Homogeneous only: the trace must be a single-class,
    single-channel round-robin stream with ways | MAX_WAYS.  Energy is
    (+,+)-linear in the ops, so on that domain the accumulator is the
    exact per-op sum — engine-independent by construction."""

    def _periodic_form(self, sim, trace) -> tuple[int, int]:
        t = np.arange(trace.n_ops)
        cls = np.asarray(trace.cls)
        if trace.arrival_us is not None and np.any(trace.arrival_us > 0):
            okay = ", ".join(sorted(
                n for n, e in _REGISTRY.items() if e.caps.arrivals))
            raise CapabilityError(
                "engine 'squaring' folds a fixed period matrix — per-op "
                f"arrivals break periodicity (arrival-aware engines: {okay})")
        if trace.extra_us is not None and np.any(trace.extra_us > 0):
            okay = ", ".join(sorted(
                n for n, e in _REGISTRY.items() if e.caps.arrivals))
            raise CapabilityError(
                "engine 'squaring' folds a fixed period matrix — per-op "
                "reliability surcharges (extra_us) break periodicity "
                f"(fault-aware engines: {okay})")
        if (trace.channels != 1
                or np.any(cls != cls[0])
                or np.any(np.asarray(trace.channel) != 0)
                or np.any(np.asarray(trace.way) != t % trace.ways)
                or np.any(np.asarray(trace.parity)
                          != (t // trace.ways) % 2)):
            hetero = ", ".join(sorted(
                n for n, e in _REGISTRY.items() if e.caps.heterogeneous))
            raise CapabilityError(
                "engine 'squaring' needs a homogeneous single-channel "
                f"round-robin stream (heterogeneous engines: {hetero})")
        _sim._validate_squaring_ways(trace.ways)
        k = int(cls[0])
        if float(np.asarray(sim.table.arb_us)[k]) != 0.0:
            raise CapabilityError(
                "engine 'squaring' models a dedicated single-channel "
                "firmware loop (arb_us must be zero)")
        return k, trace.ways

    def end_time(self, sim, trace, *, batched, segment_len):
        k, ways = self._periodic_form(sim, trace)
        fn = sim._closure(
            ("squaring", k, ways, trace.n_ops, batched),
            lambda: functools.partial(
                _sim._squaring_end_time,
                *(sim._targs[i][k] for i in range(6)),
                jnp.asarray(ways, jnp.int32),
                n_pages=trace.n_ops, batched=batched))
        return float(fn())

    def energy_sums(self, sim, trace, kind, *, batched, segment_len):
        end = self.end_time(sim, trace, batched=batched,
                            segment_len=segment_len)
        return end, sim._linear_energy_sums(trace, kind)

    def steady_channel_end(self, op, ways, *, n_pages, batched):
        _sim._validate_squaring_ways(ways)
        return _sim._squaring_end_time(
            *_op_scalars(op), jnp.asarray(ways, jnp.int32),
            n_pages=n_pages, batched=batched)

    def sweep_steady(self, scalars, data_bytes, ways, *, n_pages, batched):
        _sim._validate_squaring_ways(ways)
        return _sim._sweep_squaring_jit(*scalars, data_bytes, ways,
                                        n_pages=n_pages, batched=batched)

    def canonical_folds(self, sim):
        # canonical *periodic* domain: one op class, single channel,
        # 4-way round robin (arrivals/extras break periodicity and are
        # rejected by this engine, so the canonical request has none)
        fn = functools.partial(
            _sim._squaring_end_time,
            *(sim._targs[i][_trace.READ] for i in range(6)),
            jnp.asarray(4, jnp.int32), n_pages=64, batched=False)
        return {"end_time": (fn, ())}


@register_engine("pallas", heterogeneous=True, batched_tables=True,
                 energy=True, jittable=False, arrivals=True, ftl=True)
class PallasEngine(_EngineBase):
    """The (max,+) Pallas matrix-fold kernel (TPU-native; interpret on
    CPU).  The step-matrix dictionary is built host-side per query, so
    the session closure cache does not apply."""

    def end_time(self, sim, trace, *, batched, segment_len):
        from repro.kernels.maxplus.ops import trace_end_time_maxplus
        return float(trace_end_time_maxplus(
            sim.table, trace, policy=_policy_name(batched)))

    def energy_sums(self, sim, trace, kind, *, batched, segment_len):
        from repro.kernels.maxplus.ops import trace_energy_maxplus
        end, sums = trace_energy_maxplus(
            sim.table, trace, kind, policy=_policy_name(batched))
        return float(end), np.asarray(sums, np.float64)

    def end_time_batch(self, tables, trace, *, batched, segment_len,
                       combine="chain"):
        from repro.kernels.maxplus.ops import trace_end_time_maxplus
        return np.asarray(trace_end_time_maxplus(
            list(tables), trace, policy=_policy_name(batched)))

    def canonical_folds(self, sim):
        from repro.kernels.maxplus.ops import trace_fold_closure
        return {"end_time": trace_fold_closure(
            sim.table, _canonical_trace(), policy="eager")}


@register_engine("streaming", heterogeneous=True, batched_tables=False,
                 energy=True, jittable=True, arrivals=True, ftl=True)
class StreamingEngine(_EngineBase):
    """Constant-memory chunked fold (DESIGN.md §2.7): the trace streams
    through ``sim.trace_chunk_fold`` in fixed-size masked chunks, with
    the occupancy state tuple, the arrival origin row and the
    phase-energy accumulator carried between chunks — the segment-product
    recurrence of §2.3 specialised to its concrete carried state, so any
    chunking reproduces the scan engine *bit-for-bit* while peak live
    memory stays O(chunk) regardless of trace length.  ``segment_len``
    is the chunk length; :meth:`Simulator.run_stream` feeds this engine
    chunk iterators that never materialise the trace at all."""

    def _fold(self, sim, chunks, *, batched, kind=None, want_comp=False):
        """Fold an iterator of ``OpTrace`` chunks; returns
        ``(end_us, [P] energy sums, comp list | None, channels)``.
        Chunks are padded to power-of-two length buckets, so a stream of
        equal-size chunks compiles exactly once (plus once for a ragged
        tail bucket)."""
        e_tab = None if kind is None else sim._energy_table(kind)
        carry = None
        channels = None
        comps = [] if want_comp else None
        end = None
        for chunk in chunks:
            if chunk.n_ops == 0:
                continue
            if channels is None:
                channels = chunk.channels
                if e_tab is None:
                    e_tab = jnp.zeros((sim.table.n_classes, 2, 1),
                                      jnp.float32)
                carry = _sim.trace_chunk_init(channels, e_tab.shape[-1])
            elif chunk.channels != channels:
                raise ValueError(
                    f"streaming chunks switched geometry mid-stream: "
                    f"{chunk.channels} channels after {channels}")
            l_b = _bucket_len(chunk.n_ops)
            fn = sim._closure(
                ("stream", channels, l_b, batched, kind is not None),
                lambda channels=channels: functools.partial(
                    _sim.trace_chunk_fold, *sim._targs,
                    n_channels=channels, batched=batched))
            state, acc, end, comp = fn(
                e_tab, *_padded_trace_args(chunk, l_b),
                *_carry_args(carry))
            carry = (state, acc)
            if want_comp:
                comps.append(np.asarray(comp, np.float64)[: chunk.n_ops])
        if channels is None:
            raise ValueError("empty trace: no ops to simulate")
        return float(end), np.asarray(carry[1], np.float64), comps, channels

    def end_time(self, sim, trace, *, batched, segment_len):
        end, _, _, _ = self._fold(
            sim, _trace.iter_trace_chunks(trace, segment_len or 64),
            batched=batched)
        return end

    def energy_sums(self, sim, trace, kind, *, batched, segment_len):
        end, sums, _, _ = self._fold(
            sim, _trace.iter_trace_chunks(trace, segment_len or 64),
            batched=batched, kind=kind)
        return end, sums

    def completions(self, sim, trace, *, batched, segment_len=None):
        end, _, comps, _ = self._fold(
            sim, _trace.iter_trace_chunks(trace, segment_len or 64),
            batched=batched, want_comp=True)
        return end, np.concatenate(comps)

    def canonical_folds(self, sim):
        t = _canonical_trace()
        e_tab = jnp.zeros((sim.table.n_classes, 2, 1), jnp.float32)
        fn = functools.partial(_sim.trace_chunk_fold, *sim._targs,
                               n_channels=t.channels, batched=False)
        args = ((e_tab,) + _padded_trace_args(t, 64)
                + _carry_args(_sim.trace_chunk_init(t.channels, 1)))
        return {"chunk_fold": (fn, args)}


def _carry_args(carry):
    """Flatten the ``trace_chunk_fold`` carry back into its positional
    argument order ``(bus, chip, ctrl, round_start, energy_acc)``."""
    (bus_free, chip_free, ctrl_free, round_start), acc = carry
    return bus_free, chip_free, ctrl_free, round_start, acc


@register_engine("oracle", heterogeneous=True, batched_tables=False,
                 energy=True, jittable=False, arrivals=True, ftl=True)
class OracleEngine(_EngineBase):
    """The plain-Python event loop (``repro.core.sim_ref``) — the test
    oracle, now first-class behind the same request surface."""

    def end_time(self, sim, trace, *, batched, segment_len):
        from repro.core.sim_ref import simulate_trace_ref
        return float(simulate_trace_ref(sim.table, trace,
                                        _policy_name(batched)))

    def completions(self, sim, trace, *, batched, segment_len=None):
        from repro.core.sim_ref import simulate_trace_completions_ref
        end, comp = simulate_trace_completions_ref(
            sim.table, trace, _policy_name(batched))
        return float(end), comp

    def energy_sums(self, sim, trace, kind, *, batched, segment_len):
        from repro.core.sim_ref import simulate_trace_energy_ref
        end, sums = simulate_trace_energy_ref(
            sim.table, trace, kind, _policy_name(batched))
        return float(end), np.asarray(sums, np.float64)

    def canonical_folds(self, sim):
        # plain-Python event loop: nothing to trace — the AST layer
        # lints repro.core.sim_ref instead (DESIGN.md §2.9)
        return None


def _op_scalars(op: PageOpParams):
    return tuple(jnp.asarray(x, jnp.float32)
                 for x in (op.cmd_us, op.pre_us, op.slot_us, op.post_lo_us,
                           op.post_hi_us, op.ctrl_us))


# ---------------------------------------------------------------------------
# Request / response types
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SimRequest:
    """One simulation query.  Validation happens here, once: the policy
    literals (issue *and* scheduler), the objective and the engine name
    are all checked at request construction, so no entry point can
    silently fall through on a typo.

    Exactly one of ``trace`` (placed ops) or ``workload`` (a
    placement-free ``RequestStream``) must be given.  A workload query
    also accepts ``sched_policy``: static policies lower offline to a
    trace any engine can evaluate; dynamic policies need an engine with
    the ``dispatch`` capability (enforced by the registry) and produce
    per-request latency percentiles on the result.

    ``faults`` attaches a :class:`repro.core.faults.FaultSpec`
    (DESIGN.md §2.8): read-retry/jitter surcharges and program-fault
    remap ops are sampled once, host-side, and rewritten into the
    placed trace before the engine fold, so every engine answers the
    same faulty trace bit-deterministically given ``(query, spec)``.
    On workload queries a spec with ``hedge_fraction > 0`` also hedges
    the stream (``workload.with_hedges``) before lowering; a bare-trace
    query has no requests to hedge, so only the per-op fault channel
    applies.

    ``ftl`` attaches a :class:`repro.core.ftl.FTLSpec` (DESIGN.md
    §2.10): the workload's logical addresses run through the L2P map
    first, GC relocation and erase ops are injected into the stream,
    and the translated stream lowers through the same scheduler and
    engines as everything else — the result additionally reports
    ``waf`` / ``gc_op_count`` / ``free_page_low_watermark`` /
    ``fresh_mb_s``.  FTL queries need the ``ftl`` capability (the
    translated stream uses the extended 7-class op table)."""

    trace: OpTrace | None = None
    policy: Policy | None = None        # None -> the session's default
    objective: Objective = "end_time"
    engine: str | None = None           # None -> "scan"
    segment_len: int | None = 64        # prefix-engine chunk size
    workload: RequestStream | None = None
    sched_policy: str | None = None     # None -> "stripe" (workload only)
    faults: FaultSpec | None = None     # None -> fault-free
    ftl: "_ftl.FTLSpec | None" = None   # None -> address-free (no FTL)

    def __post_init__(self):
        if (self.trace is None) == (self.workload is None):
            raise ValueError("SimRequest needs exactly one of trace= or "
                             "workload=")
        if self.ftl is not None:
            if self.workload is None:
                raise ValueError(
                    "ftl= applies to workload requests (a placed trace "
                    "has no logical addresses left to translate)")
            if not isinstance(self.ftl, _ftl.FTLSpec):
                raise ValueError(
                    f"ftl= takes an FTLSpec, got {type(self.ftl).__name__}")
        if self.sched_policy is not None:
            if self.workload is None:
                raise ValueError("sched_policy applies to workload "
                                 "requests (the trace is already placed)")
            _sched.policy_is_dynamic(self.sched_policy)   # validates
        if self.faults is not None and not isinstance(self.faults,
                                                      FaultSpec):
            raise ValueError(
                f"faults= takes a FaultSpec, got {type(self.faults).__name__}")
        if (self.faults is not None and self.trace is not None
                and self.trace.extra_us is not None):
            raise ValueError(
                "trace already carries extra_us — faults were already "
                "applied (attach the FaultSpec OR pre-apply, not both)")
        if self.policy is not None:
            policy_is_batched(self.policy)
        if self.objective not in OBJECTIVES:
            raise ValueError(f"unknown objective {self.objective!r} "
                             f"(one of {', '.join(OBJECTIVES)})")
        if self.engine is not None:
            get_engine(self.engine)


@dataclasses.dataclass(frozen=True, eq=False)
class SimResult:
    """One simulation answer — the same shape for every engine and
    objective.  ``energy`` is populated for objective "energy"/"all";
    ``mb_s`` is user-payload bandwidth (None for payload-free traces,
    e.g. all-hedged duplicates).  Workload queries additionally carry
    per-request latencies (when the serving engine emits per-op
    completions — scan / oracle / every dynamic dispatch; the log-depth
    engines answer makespan-only and leave it None).  Fault-injected
    queries additionally carry the sampled ``retry_hist`` (retry-count
    histogram over read ops) and ``n_remap_ops`` (program-fault remap
    writes inserted by the rewrite pass).

    Percentile properties are guarded: a pN on fewer than
    ``100 / (100 - N)`` requests (e.g. p99 on < 100, p99.9 on < 1000)
    is below the percentile resolution — it clamps to the max observed
    latency and emits a ``RuntimeWarning`` instead of silently
    interpolating a tail that was never sampled; an empty latency
    stream answers NaN."""

    end_us: float
    mb_s: float | None
    channel_busy_us: np.ndarray          # [channels] bus occupancy (us)
    energy: EnergyBreakdown | None
    engine: str
    n_ops: int
    payload_bytes: int
    request_lat_us: np.ndarray | None = None   # [R] per-request latency
    sched_policy: str | None = None            # workload queries only
    retry_hist: np.ndarray | None = None       # [max_retries+1] counts
    n_remap_ops: int = 0                       # program-fault remap writes
    # FTL queries only (DESIGN.md §2.10): write amplification, injected
    # GC traffic, the free-pool low watermark, and the fresh-drive
    # bandwidth of the same host stream (mb_s is the aged/steady-state
    # number once GC competes for the bus)
    waf: float | None = None                   # pages written / host pages
    gc_op_count: int | None = None             # GC reads + writes + erases
    free_page_low_watermark: int | None = None
    fresh_mb_s: float | None = None            # host-only (GC-free) MB/s
    ftl_stats: "_ftl.FTLStats | None" = None   # full FTL counter block

    @property
    def channel_occupancy(self) -> np.ndarray:
        """Per-channel bus busy fraction of the makespan."""
        return self.channel_busy_us / max(self.end_us, 1e-30)

    def _latency_percentile(self, q: float) -> float | None:
        """Guarded percentile (see class docstring): clamps to the max
        latency (with a RuntimeWarning) when the stream is too short to
        resolve the requested tail; NaN on an empty stream."""
        if self.request_lat_us is None:
            return None
        lat = np.asarray(self.request_lat_us, np.float64)
        if lat.size == 0:
            return float("nan")
        # resolving pN needs >= 100/(100-N) samples: below that the
        # order statistic for the tail does not exist yet
        if lat.size * (100.0 - q) < 100.0:
            warnings.warn(
                f"p{q:g} on {lat.size} request(s) is below the percentile "
                "resolution — clamping to the max observed latency",
                RuntimeWarning, stacklevel=3)
            return float(np.max(lat))
        return float(np.percentile(lat, q))

    @property
    def p50_us(self) -> float | None:
        """Median request latency (workload queries with completions)."""
        return self._latency_percentile(50)

    @property
    def p99_us(self) -> float | None:
        """99th-percentile request latency."""
        return self._latency_percentile(99)

    @property
    def p99_9_us(self) -> float | None:
        """99.9th-percentile request latency — the retry-storm tail the
        reliability layer exists to measure (DESIGN.md §2.8)."""
        return self._latency_percentile(99.9)

    def describe(self) -> str:
        occ = "/".join(f"{x:.2f}" for x in self.channel_occupancy)
        bw = f"{self.mb_s:.1f} MB/s" if self.mb_s is not None else "no payload"
        lat = ("" if self.request_lat_us is None else
               f", p50/p99 {self.p50_us:.0f}/{self.p99_us:.0f} us")
        ftl = ("" if self.waf is None else
               f", WAF {self.waf:.2f} ({self.gc_op_count} GC ops)")
        return (f"[{self.engine}] {self.n_ops} ops in "
                f"{self.end_us / 1e3:.2f} ms, {bw}, occ {occ}{lat}{ftl}")


@dataclasses.dataclass(frozen=True)
class CacheInfo:
    hits: int
    misses: int
    entries: int
    evictions: int = 0
    max_entries: int | None = None


# ---------------------------------------------------------------------------
# The session
# ---------------------------------------------------------------------------


class Simulator:
    """A simulation session bound to one design point.

    Binds an ``SSDConfig`` (or a raw ``OpClassTable``) once: the timing
    table is converted to device arrays at construction, and every
    jittable engine's closures are cached on
    ``(engine, geometry, trace-length bucket, policy, ...)`` so repeated
    queries are compile- and conversion-free.  All five registered
    engines answer through :meth:`run`; :meth:`run_many` is the batched
    serving path (length-bucketed, vmapped); :meth:`sweep` fans one
    trace out over a batch of design-point tables.
    """

    def __init__(self, config: SSDConfig | None = None, *,
                 table: OpClassTable | None = None,
                 kind: InterfaceKind | str | None = None,
                 max_cache_entries: int | None = 512,
                 max_ftl_sessions: int | None = 8):
        if config is None and table is None:
            raise ValueError("Simulator needs an SSDConfig or an "
                             "OpClassTable")
        if max_cache_entries is not None and max_cache_entries < 1:
            raise ValueError("max_cache_entries must be >= 1 or None "
                             f"(unbounded), got {max_cache_entries}")
        if max_ftl_sessions is not None and max_ftl_sessions < 1:
            raise ValueError("max_ftl_sessions must be >= 1 or None "
                             f"(unbounded), got {max_ftl_sessions}")
        self.config = config
        self.table = table if table is not None else op_class_table(config)
        if kind is not None:
            self.kind: InterfaceKind | None = InterfaceKind(kind)
        else:
            self.kind = config.interface if config is not None else None
        self.default_policy: Policy = (config.policy if config is not None
                                       else "eager")
        self._targs = tuple(jnp.asarray(getattr(self.table, f))
                            for f in _TABLE_FIELDS)
        self._e_tables: dict[InterfaceKind, jax.Array] = {}
        self._e_tables_np: dict[InterfaceKind, np.ndarray] = {}
        self.max_cache_entries = max_cache_entries
        self.max_ftl_sessions = max_ftl_sessions
        self._ftl_sessions: collections.OrderedDict[tuple, "Simulator"] \
            = collections.OrderedDict()
        self._ftl_hits = 0
        self._ftl_misses = 0
        self._ftl_evictions = 0
        self._closures: collections.OrderedDict[tuple, object] = \
            collections.OrderedDict()
        self._hits = 0
        self._misses = 0
        self._evictions = 0
        # fused aged-sweep memos (DESIGN.md §2.11): preconditioned scan
        # states per spec batch (a pure function of the specs — the
        # host translator re-ages per call by design, the compiled
        # sweep ages once) and learned (t_max, t2) buffer sizes per
        # (specs, stream) so warm sweeps run exactly-sized folds with
        # no grow-and-retry replay.
        self._ftl_pre_states: collections.OrderedDict[tuple, object] = \
            collections.OrderedDict()
        self._ftl_sweep_sizes: collections.OrderedDict[
            tuple, tuple[int, int]] = collections.OrderedDict()
        #: ``run`` and ``sweep`` calls so far: the ``call`` stat of
        #: their root spans
        self._calls = 0

    # -- shared per-config sessions ----------------------------------------

    @classmethod
    def for_config(cls, config: SSDConfig) -> "Simulator":
        """Process-wide memoised session for a design point — the
        storage tier, planners and benchmarks all share closures."""
        return simulator_for(config)

    # -- closure cache ------------------------------------------------------

    def _closure(self, key: tuple, build):
        """LRU-bounded jit-closure cache: hits refresh recency, misses
        build and (past ``max_cache_entries``) evict the least recently
        used closure — a long-lived session sweeping many geometries and
        length buckets holds a bounded working set instead of growing
        without limit."""
        fn = self._closures.get(key)
        if fn is None:
            self._misses += 1
            fn = self._closures[key] = build()
            if (self.max_cache_entries is not None
                    and len(self._closures) > self.max_cache_entries):
                self._closures.popitem(last=False)
                self._evictions += 1
        else:
            self._hits += 1
            self._closures.move_to_end(key)
        return fn

    def cache_info(self) -> CacheInfo:
        return CacheInfo(self._hits, self._misses, len(self._closures),
                         self._evictions, self.max_cache_entries)

    def cache_clear(self) -> None:
        self._closures.clear()
        self._hits = self._misses = self._evictions = 0

    def _energy_table(self, kind: InterfaceKind) -> jax.Array:
        e = self._e_tables.get(kind)
        if e is None:
            e = self._e_tables[kind] = jnp.asarray(
                op_phase_energy_uj(self.table, kind))
        return e

    def _linear_energy_sums(self, trace: OpTrace,
                            kind: InterfaceKind) -> np.ndarray:
        """[P] phase sums (uJ) by direct per-op summation — energy is
        (+,+)-linear, so this is the engine-free evaluation the packed
        serving path uses.  The float64 phase table is memoised per
        interface kind like its device-array twin."""
        e = self._e_tables_np.get(kind)
        if e is None:
            e = self._e_tables_np[kind] = np.asarray(
                op_phase_energy_uj(self.table, kind), np.float64)
        return e[np.asarray(trace.cls),
                 np.asarray(trace.parity) % 2].sum(axis=0)

    # -- queries ------------------------------------------------------------

    def _resolve(self, request: SimRequest, trace: OpTrace | None = None):
        policy = request.policy or self.default_policy
        batched = policy_is_batched(policy)
        eng = get_engine(request.engine or "scan")
        if request.objective in ("energy", "all"):
            if not eng.caps.energy:
                raise CapabilityError(
                    f"engine {eng.caps.name!r} does not accumulate energy")
            if self.kind is None:
                raise ValueError(
                    "energy query on a Simulator with no interface kind "
                    "(pass kind= or bind an SSDConfig)")
        if (trace is not None and trace.arrival_us is not None
                and np.any(trace.arrival_us > 0) and not eng.caps.arrivals):
            okay = ", ".join(n for n in registered_engines()
                             if _REGISTRY[n].caps.arrivals)
            raise CapabilityError(
                f"engine {eng.caps.name!r} cannot consume arrival-aware "
                f"traces (engines that can: {okay})")
        # faults ride the same per-op side-channel machinery as arrivals,
        # so the capability row is shared
        if ((request.faults is not None and not request.faults.is_zero
             or trace is not None and trace.extra_us is not None
             and np.any(trace.extra_us > 0)) and not eng.caps.arrivals):
            okay = ", ".join(n for n in registered_engines()
                             if _REGISTRY[n].caps.arrivals)
            raise CapabilityError(
                f"engine {eng.caps.name!r} cannot consume fault-extended "
                f"traces (engines that can: {okay})")
        if request.ftl is not None and not eng.caps.ftl:
            okay = ", ".join(n for n in registered_engines()
                             if _REGISTRY[n].caps.ftl)
            raise CapabilityError(
                f"engine {eng.caps.name!r} cannot consume FTL-translated "
                f"streams (engines that can: {okay})")
        return eng, batched

    def _result(self, trace: OpTrace, end_us: float, engine: str,
                energy: EnergyBreakdown | None,
                request_lat_us: np.ndarray | None = None,
                sched_policy: str | None = None,
                sampler: FaultSampler | None = None) -> SimResult:
        table = self.table
        payload = trace.total_bytes(table)
        busy = np.bincount(
            np.asarray(trace.channel),
            weights=np.asarray(table.slot_us, np.float64)[
                np.asarray(trace.cls)],
            minlength=trace.channels)
        return SimResult(
            end_us=end_us,
            mb_s=(payload / end_us) if payload > 0 else None,
            channel_busy_us=busy, energy=energy, engine=engine,
            n_ops=trace.n_ops, payload_bytes=payload,
            request_lat_us=request_lat_us, sched_policy=sched_policy,
            retry_hist=(None if sampler is None
                        else sampler.retry_hist.copy()),
            n_remap_ops=0 if sampler is None else sampler.n_remap_ops)

    def _breakdown(self, sums, end_us: float, trace: OpTrace):
        return breakdown_from_sums(
            sums, end_us=end_us,
            payload_bytes=trace.total_bytes(self.table),
            kind=self.kind, channels=trace.channels)

    def run(self, request: SimRequest | OpTrace | RequestStream, /,
            **overrides) -> SimResult:
        """Answer one query.  Accepts a :class:`SimRequest`, a bare
        ``OpTrace``, or a bare ``RequestStream`` (a workload query under
        ``sched_policy``, default static stripe) plus request fields as
        keywords."""
        if isinstance(request, RequestStream):
            request = SimRequest(workload=request, **overrides)
        elif not isinstance(request, SimRequest):
            request = SimRequest(trace=request, **overrides)
        elif overrides:
            request = dataclasses.replace(request, **overrides)
        self._calls += 1
        ops = (request.trace.n_ops if request.workload is None
               else request.workload.n_requests)
        with _spans.span("run", call=self._calls, ops=ops, points=1):
            if request.workload is not None:
                return self._run_workload(request)
            return self._run_trace(request)

    def _run_trace(self, request: SimRequest) -> SimResult:
        """Trace queries: an already placed ``OpTrace`` on one engine."""
        trace = request.trace
        if trace.n_ops == 0:
            raise ValueError("empty trace: no ops to simulate")
        trace.validate_against(self.table)
        eng, batched = self._resolve(request, trace)
        sampler = None
        if request.faults is not None:
            trace, _, sampler = _sched.apply_faults(
                trace, request.faults, self.table)
        energy = None
        if request.objective in ("energy", "all"):
            end, sums = eng.energy_sums(
                self, trace, self.kind, batched=batched,
                segment_len=request.segment_len)
            energy = self._breakdown(sums, end, trace)
            end_us = end
        else:
            end_us = eng.end_time(self, trace, batched=batched,
                                  segment_len=request.segment_len)
        with _spans.span("extract"):
            return self._result(trace, end_us, eng.caps.name, energy,
                                sampler=sampler)

    def _ftl_session(self, spec: "_ftl.FTLSpec") -> "Simulator":
        """Memoised sibling session over the 7-class FTL op table
        (DESIGN.md §2.10) — keyed on the fields that shape the table, so
        GC-policy / overprovisioning sweeps at fixed timing share one
        session's jitted closures."""
        key = (float(spec.map_us),
               None if spec.erase_us is None else float(spec.erase_us))
        sess = self._ftl_sessions.get(key)
        if sess is None:
            self._ftl_misses += 1
            sess = self._ftl_sessions[key] = Simulator(
                self.config,
                table=_ftl.ftl_op_class_table(self.config, spec),
                max_cache_entries=self.max_cache_entries)
            if (self.max_ftl_sessions is not None
                    and len(self._ftl_sessions) > self.max_ftl_sessions):
                self._ftl_sessions.popitem(last=False)
                self._ftl_evictions += 1
        else:
            self._ftl_hits += 1
            self._ftl_sessions.move_to_end(key)
        return sess

    def _page_bytes(self) -> int:
        """User bytes of one flash page of the bound drive."""
        return _nand_chip(self.config.cell).page_data_bytes

    def ftl_cache_info(self) -> CacheInfo:
        """Counters for the FTL sub-session cache — same shape as
        :meth:`cache_info`, but one entry here is a whole sibling
        ``Simulator`` (its own 7-class table device arrays and closure
        cache), so the bound is deliberately small."""
        return CacheInfo(self._ftl_hits, self._ftl_misses,
                         len(self._ftl_sessions), self._ftl_evictions,
                         self.max_ftl_sessions)

    def _run_workload_ftl(self, request: SimRequest) -> SimResult:
        """FTL workload queries (DESIGN.md §2.10): the host stream runs
        through the L2P translation stage first — GC relocation and
        erase ops are injected on free-pool pressure and every op gets
        an FTL op class carrying the firmware map cost — then the
        translated stream lowers through the same scheduler / engine
        machinery as any other workload (all ops, GC included, compete
        for placement slots and bus time).  A second host-only pass over
        the same translation prices the fresh-drive bandwidth, so the
        aged-vs-fresh cliff is part of the one answer.

        Block-level program/erase failures are *owned by the FTL
        accounting* (bad blocks retire through the same valid-count
        bookkeeping GC uses); the fault sampler here only prices the
        per-op retry/jitter surcharges, against a read/write view of the
        translated classes."""
        spec = request.ftl
        stream = request.workload
        fspec = request.faults
        if fspec is not None and fspec.hedge_fraction > 0.0:
            stream = _workload.with_hedges(
                stream, fspec.hedge_fraction,
                after_us=fspec.hedge_after_us or 0.0, seed=fspec.seed)
        sess = self._ftl_session(spec)
        eng, batched = sess._resolve(request)
        policy_s = request.sched_policy or "stripe"
        dynamic = _sched.policy_is_dynamic(policy_s)
        if dynamic and batched:
            raise ValueError(
                "dynamic dispatch is FCFS under the eager issue "
                "policy; 'batched' rounds are fixed at build time "
                "and only exist for static lowerings")
        channels, ways = self.config.channels, self.config.ways
        if fspec is None or (fspec.prog_fail_prob == 0.0
                             and fspec.erase_fail_prob == 0.0):
            # default path: the compiled lax.scan translation engine
            # (DESIGN.md §2.11) — exact-agreement twin of the host
            # translator, regression-pinned op-for-op in the tests
            translation = _ftl_scan.translate_scan(
                stream, spec, page_bytes=self._page_bytes())
        else:
            # block-level program/erase failures draw RNG per attempt —
            # host-oracle territory (the scan folds stay RNG-free)
            translation = _ftl.translate(
                stream, spec,
                prog_fail_prob=fspec.prog_fail_prob,
                erase_fail_prob=fspec.erase_fail_prob,
                fault_seed=fspec.seed, page_bytes=self._page_bytes())
        extra = None
        sampler = None
        if fspec is not None:
            # block-level failures were consumed by translate() above;
            # the per-op channel prices retries/jitter on a host-class
            # view of the translated stream (GC reads retry like reads)
            neutered = dataclasses.replace(
                fspec, prog_fail_prob=0.0, erase_fail_prob=0.0)
            if not neutered.is_zero:
                sampler = FaultSampler(neutered, channels, ways, sess.table)
                cls_view = np.where(
                    np.isin(translation.op_cls,
                            (_ftl.FTL_READ, _ftl.GC_READ)),
                    _trace.READ, _trace.WRITE).astype(np.int32)
                extra, _, _ = sampler.sample(cls_view)

        def evaluate(mask=None, want_comp=False):
            cls = translation.op_cls
            arr = translation.arrival_us
            pay = translation.payload
            ext = extra
            if mask is not None:
                cls, arr, pay = cls[mask], arr[mask], pay[mask]
                ext = None if ext is None else ext[mask]
            if dynamic:
                end, comp, chan, way, par = eng.dispatch_run(
                    sess, cls, arr, n_channels=channels, n_ways=ways,
                    rule=policy_s, extra_us=ext, retired=None)
                tr = OpTrace(
                    cls=np.asarray(cls, np.int32), channel=chan, way=way,
                    parity=par, channels=channels, ways=ways,
                    payload=None if pay.all() else pay,
                    arrival_us=np.asarray(arr, np.float32),
                    extra_us=(None if ext is None
                              else np.asarray(ext, np.float32)))
                return tr, end, comp
            with _spans.span("lower", ops=len(cls), ranked_ops=0):
                tr = _sched.lower_ops(cls, arr, channels, ways, policy_s,
                                      payload=pay)
                if ext is not None:
                    tr = dataclasses.replace(
                        tr, extra_us=np.asarray(ext, np.float32))
                tr.validate_against(sess.table)
            fold_kw = {}
            if stream.sectored and isinstance(eng, ScanEngine):
                # a sector stream's op counts move with its draw of sizes
                # and RMW reads: 1/16 headroom keeps draws of one size in
                # one bucket, so they share one compiled fold
                fold_kw["steps"] = len(cls) + len(cls) // 16
            base = getattr(_EngineBase, "completions")
            if want_comp and getattr(type(eng), "completions",
                                     base) is not base:
                end, comp = eng.completions(
                    sess, tr, batched=batched,
                    segment_len=request.segment_len, **fold_kw)
                return tr, end, comp
            end = eng.end_time(sess, tr, batched=batched,
                               segment_len=request.segment_len, **fold_kw)
            return tr, end, None

        trace, end_us, comp = evaluate(want_comp=True)
        lat = None
        if comp is not None:
            # GC ops belong to no request (request_id -1): latency
            # accounting sees host ops only — but over the *aged*
            # completion times, so GC queueing is in the tail
            with _spans.span("extract"):
                host = translation.request_id >= 0
                lowered = LoweredWorkload(
                    trace=trace, request_id=translation.request_id[host],
                    request_arrival_us=np.asarray(stream.arrival_us,
                                                  np.float32))
                lat = _payload_latencies(lowered, np.asarray(comp)[host],
                                         stream)
        energy = None
        if request.objective in ("energy", "all"):
            # energy is (+,+)-linear, so the engine-free per-op sum is
            # exact for the translated trace too (DESIGN.md §2.4)
            energy = sess._breakdown(
                sess._linear_energy_sums(trace, sess.kind), end_us, trace)
        fresh_mb_s = None
        if bool(translation.gc.any()):
            # fresh-drive reference: the host ops alone (map cost still
            # charged — FTL classes are kept), no GC competition
            _, fresh_end, _ = evaluate(mask=~translation.gc)
            fresh_payload = trace.total_bytes(sess.table)
            if fresh_payload > 0:
                fresh_mb_s = fresh_payload / fresh_end
        stats = translation.stats
        with _spans.span("extract"):
            res = sess._result(trace, end_us, eng.caps.name, energy,
                               request_lat_us=lat, sched_policy=policy_s,
                               sampler=sampler)
        return dataclasses.replace(
            res, waf=stats.waf, gc_op_count=stats.gc_op_count,
            free_page_low_watermark=stats.free_page_low_watermark,
            fresh_mb_s=fresh_mb_s, ftl_stats=stats)

    def _run_workload(self, request: SimRequest) -> SimResult:
        """Workload queries: lower the request stream through the
        scheduler (static policies offline, dynamic policies as the
        joint dispatch fold) and attach per-request latencies when the
        engine emits per-op completions (DESIGN.md §2.6)."""
        if self.config is None:
            raise ValueError(
                "workload queries need a Simulator bound to an SSDConfig "
                "(the scheduler needs the channel/way geometry)")
        stream = request.workload
        if stream.n_requests == 0:
            raise ValueError("empty workload: no requests to simulate")
        if request.ftl is not None:
            return self._run_workload_ftl(request)
        if int(np.max(stream.op_cls)) >= self.table.n_classes:
            # checked before the dispatch fold runs: a clamped-garbage
            # simulation followed by a numpy IndexError is not a report
            raise ValueError(
                f"RequestStream.op_cls out of range: max "
                f"{int(np.max(stream.op_cls))} >= table.n_classes "
                f"{self.table.n_classes}")
        spec = request.faults
        if spec is not None and spec.hedge_fraction > 0.0:
            # the spec's mitigation half: hedge payload reads before the
            # scheduler sees the stream, so duplicates flow through the
            # same lowering/dispatch as everything else
            stream = _workload.with_hedges(
                stream, spec.hedge_fraction,
                after_us=spec.hedge_after_us or 0.0, seed=spec.seed)
        policy_s = request.sched_policy or "stripe"
        eng, batched = self._resolve(request)
        channels, ways = self.config.channels, self.config.ways
        if _sched.policy_is_dynamic(policy_s):
            # registry-enforced: engines without the dispatch capability
            # raise CapabilityError naming the ones that have it
            if batched:
                raise ValueError(
                    "dynamic dispatch is FCFS under the eager issue "
                    "policy; 'batched' rounds are fixed at build time "
                    "and only exist for static lowerings")
            cls, arrival, req_id, payload = request_ops(stream)
            extra = retired = sampler = None
            if spec is not None:
                # dynamic faults sample on the op-class sequence alone
                # (placement is decided in-fold): retry/jitter surcharges
                # ride extra_us, a program fault inserts its remap write
                # right after the failed op, and retired blocks become a
                # dispatch constraint via the retired mask
                sampler = FaultSampler(spec, channels, ways, self.table)
                extra, write_fail, _ = sampler.sample(cls)
                fail = np.flatnonzero(write_fail)
                if len(fail):
                    ins = fail + 1
                    n = len(cls)
                    new_of_old = np.arange(n) + np.searchsorted(
                        ins, np.arange(n), "right")
                    cls = np.insert(cls, ins, cls[fail])
                    arrival = np.insert(arrival, ins, arrival[fail])
                    req_id = np.insert(req_id, ins, req_id[fail])
                    extra = np.insert(extra, ins, 0.0).astype(np.float32)
                    pay2 = np.insert(payload, ins, payload[fail])
                    # the failed original keeps its bus/cell cost but the
                    # byte credit moves to the remap — totals conserved
                    pay2[new_of_old[fail]] = False
                    payload = pay2
                    sampler.n_remap_ops += len(fail)
                if sampler.retired.any():
                    retired = sampler.retired
            end, comp, chan, way, par = eng.dispatch_run(
                self, cls, arrival, n_channels=channels, n_ways=ways,
                rule=policy_s, extra_us=extra, retired=retired)
            trace = OpTrace(
                cls=np.asarray(cls, np.int32), channel=chan, way=way,
                parity=par, channels=channels, ways=ways,
                payload=None if payload.all() else payload,
                arrival_us=arrival,
                extra_us=(None if extra is None
                          else np.asarray(extra, np.float32)))
            with _spans.span("extract"):
                lowered = LoweredWorkload(
                    trace=trace, request_id=req_id,
                    request_arrival_us=np.asarray(stream.arrival_us,
                                                  np.float32))
                lat = _payload_latencies(lowered, comp, stream)
            energy = None
            if request.objective in ("energy", "all"):
                # energy is (+,+)-linear: the dispatched placement fixes
                # the parity sequence, so the engine-free per-op sum is
                # exact (DESIGN.md §2.4)
                energy = self._breakdown(
                    self._linear_energy_sums(trace, self.kind), end, trace)
            with _spans.span("extract"):
                return self._result(trace, end, eng.caps.name, energy,
                                    request_lat_us=lat,
                                    sched_policy=policy_s, sampler=sampler)
        with _spans.span("lower", ops=stream.n_requests) as sp:
            lowered = _sched.lower_static(stream, channels, ways, policy_s)
            sp.set_metadata(ranked_ops=lowered.ranked_ops)
            trace = lowered.trace
            sampler = None
            if spec is not None:
                trace, rid2, sampler = _sched.apply_faults(
                    trace, spec, self.table, request_id=lowered.request_id)
                lowered = LoweredWorkload(
                    trace=trace, request_id=rid2,
                    request_arrival_us=lowered.request_arrival_us)
            trace.validate_against(self.table)
        energy = None
        lat = None
        base = getattr(_EngineBase, "completions")
        if getattr(type(eng), "completions", base) is not base:
            end_us, comp = eng.completions(self, trace, batched=batched,
                                           segment_len=request.segment_len)
            with _spans.span("extract"):
                lat = _payload_latencies(lowered, comp, stream)
        else:   # makespan-only engines (log-depth forms)
            end_us = eng.end_time(self, trace, batched=batched,
                                  segment_len=request.segment_len)
        if request.objective in ("energy", "all"):
            end_e, sums = eng.energy_sums(
                self, trace, self.kind, batched=batched,
                segment_len=request.segment_len)
            energy = self._breakdown(sums, end_e, trace)
        with _spans.span("extract"):
            return self._result(trace, end_us, eng.caps.name, energy,
                                request_lat_us=lat, sched_policy=policy_s,
                                sampler=sampler)

    def run_many(self, traces, *, policy: Policy | None = None,
                 objective: Objective = "end_time",
                 engine: str | None = None,
                 segment_len: int | None = 64,
                 shard: bool | None = None) -> list[SimResult]:
        """The batched serving path: pack heterogeneous traces into
        power-of-two length buckets per (channels, bucket) group and
        evaluate each group in one vmapped masked fold — results are
        identical to per-trace :meth:`run` (masked padding is a state
        no-op).  The bucket grid is derived from the traces actually
        present: empty power-of-two buckets are never compiled, and each
        group's *batch* dimension also rounds up to a power of two (with
        all-invalid padding rows) so batch-size jitter between calls
        reuses the compiled fold instead of recompiling per group size.

        ``engine="pallas"`` evaluates each (channels, ways) group as ONE
        fused megakernel launch over the union combo dictionary and all
        length buckets (``repro.kernels.maxplus.ops.
        run_many_end_time_maxplus``); other engines fall back to a
        per-trace loop through the same session cache.  With more than
        one device present (``shard=None`` auto / ``shard=True``), the
        scan groups additionally shard their batch rows across devices
        with ``jax.shard_map``; ``shard=False`` forces the single-device
        vmap path."""
        if objective not in OBJECTIVES:
            raise ValueError(f"unknown objective {objective!r} "
                             f"(one of {', '.join(OBJECTIVES)})")
        policy = policy or self.default_policy
        batched = policy_is_batched(policy)
        name = engine or "scan"
        get_engine(name)            # raises on unknown engine names
        traces = list(traces)
        for t in traces:
            if t.n_ops == 0:
                raise ValueError("empty trace: no ops to simulate")
            t.validate_against(self.table)
        if name not in ("scan", "pallas"):
            return [self.run(SimRequest(trace=t, policy=policy,
                                        objective=objective, engine=name,
                                        segment_len=segment_len))
                    for t in traces]
        if objective in ("energy", "all") and self.kind is None:
            raise ValueError(
                "energy query on a Simulator with no interface kind "
                "(pass kind= or bind an SSDConfig)")
        ends = np.empty(len(traces), np.float64)
        if name == "pallas":
            from repro.kernels.maxplus.ops import run_many_end_time_maxplus
            pgroups: dict[tuple[int, int], list[int]] = {}
            for i, t in enumerate(traces):
                pgroups.setdefault((t.channels, t.ways), []).append(i)
            for _, idxs in pgroups.items():
                ends[idxs] = run_many_end_time_maxplus(
                    self.table, [traces[i] for i in idxs],
                    policy=_policy_name(batched))
            return self._many_results(traces, ends, name, objective)
        mesh = _points_mesh() if shard is not False else None
        groups: dict[tuple[int, int], list[int]] = {}
        for i, t in enumerate(traces):
            groups.setdefault((t.channels, _bucket_len(t.n_ops)),
                              []).append(i)
        for (channels, t_b), idxs in groups.items():
            b_pad = _bucket_len(len(idxs), floor=1)
            if mesh is not None:        # whole rows per device shard
                n_dev = int(mesh.devices.size)
                b_pad = max(b_pad, -(-b_pad // n_dev) * n_dev)
            rows = [_pad_trace_np(traces[i], t_b) for i in idxs]
            pad_row = tuple(np.zeros_like(col) for col in rows[0])
            rows += [pad_row] * (b_pad - len(idxs))
            stacked = [np.stack(cols) for cols in zip(*rows)]
            if mesh is None:
                fn = self._closure(
                    ("scan-many", channels, t_b, batched, b_pad),
                    lambda channels=channels: functools.partial(
                        _sim.trace_end_time_masked_many, *self._targs,
                        n_channels=channels, batched=batched))
            else:
                fn = self._closure(
                    ("scan-many-shard", channels, t_b, batched, b_pad,
                     mesh.devices.size),
                    lambda channels=channels: _shard_points(
                        mesh, functools.partial(
                            _sim.trace_end_time_masked_many, *self._targs,
                            n_channels=channels, batched=batched),
                        n_sharded=7))
            ends[idxs] = np.asarray(
                fn(*(jnp.asarray(s) for s in stacked)))[: len(idxs)]
        return self._many_results(traces, ends, name, objective)

    def _many_results(self, traces, ends, name: str,
                      objective: Objective) -> list[SimResult]:
        """Assemble per-trace results for the packed serving paths:
        energy is (+,+)-linear, so the engine-free per-op sum is exact
        for every serving engine (DESIGN.md §2.4)."""
        results = []
        for t, end in zip(traces, ends):
            energy = None
            if objective in ("energy", "all"):
                energy = breakdown_from_sums(
                    self._linear_energy_sums(t, self.kind),
                    end_us=float(end),
                    payload_bytes=t.total_bytes(self.table),
                    kind=self.kind, channels=t.channels)
            results.append(self._result(t, float(end), name, energy))
        return results

    def run_stream(self, chunks, *, policy: Policy | None = None,
                   objective: Objective = "end_time", ftl=None,
                   faults: FaultSpec | None = None,
                   sched_policy: str = "stripe") -> SimResult:
        """Constant-memory streaming query (DESIGN.md §2.7): fold an
        *iterator of OpTrace chunks* (``trace.iter_trace_chunks``, a
        generator builder like ``trace.mixed_trace_chunks``, or any
        iterable) through the streaming engine without ever holding the
        full trace — payload bytes, per-channel occupancy and the op
        count accumulate chunk-by-chunk, so a million-op trace costs
        O(chunk) memory end to end.

        With ``ftl=`` (an :class:`FTLSpec`), ``chunks`` is instead an
        iterator of host :class:`RequestStream` chunks: each chunk runs
        the scan translation engine carrying the drive state
        (DESIGN.md §2.11), lowers at the carried placement-slot offset
        (``sched.lower_ops_chunk``) and feeds the same streaming fold —
        so a million-request aging trace is translated, placed and
        simulated without ever materialising the aged op stream, and
        the result (stats included) is bit-identical to the one-shot
        path.  ``faults`` prices per-op retry/jitter surcharges with
        one sequential sampler across chunks (§2.8); hedging and
        block-level program/erase failures are one-shot-only."""
        if objective not in OBJECTIVES:
            raise ValueError(f"unknown objective {objective!r} "
                             f"(one of {', '.join(OBJECTIVES)})")
        if ftl is not None:
            return self._run_stream_ftl(
                chunks, ftl, policy=policy, objective=objective,
                faults=faults, sched_policy=sched_policy)
        if faults is not None:
            raise ValueError(
                "run_stream(faults=...) needs ftl= (op-trace chunks are "
                "already placed; apply sched.apply_faults per chunk "
                "instead)")
        policy = policy or self.default_policy
        batched = policy_is_batched(policy)
        kind = None
        if objective in ("energy", "all"):
            if self.kind is None:
                raise ValueError(
                    "energy query on a Simulator with no interface kind "
                    "(pass kind= or bind an SSDConfig)")
            kind = self.kind
        eng = get_engine("streaming")
        stats = {"n_ops": 0, "payload": 0, "busy": None}
        slot = np.asarray(self.table.slot_us, np.float64)

        def tap(cs):
            for c in cs:
                if c.n_ops == 0:
                    continue
                c.validate_against(self.table)
                if stats["busy"] is None:
                    stats["busy"] = np.zeros(c.channels)
                elif len(stats["busy"]) != c.channels:
                    raise ValueError(
                        f"streaming chunks switched geometry mid-stream: "
                        f"{c.channels} channels after {len(stats['busy'])}")
                stats["n_ops"] += c.n_ops
                stats["payload"] += c.total_bytes(self.table)
                stats["busy"] += np.bincount(
                    np.asarray(c.channel),
                    weights=slot[np.asarray(c.cls)],
                    minlength=c.channels)
                yield c

        end, sums, _, channels = eng._fold(self, tap(chunks),
                                           batched=batched, kind=kind)
        energy = None
        if kind is not None:
            energy = breakdown_from_sums(
                sums, end_us=end, payload_bytes=stats["payload"],
                kind=kind, channels=channels)
        payload = stats["payload"]
        return SimResult(
            end_us=end, mb_s=(payload / end) if payload > 0 else None,
            channel_busy_us=stats["busy"], energy=energy,
            engine="streaming", n_ops=stats["n_ops"],
            payload_bytes=payload)

    def _run_stream_ftl(self, chunks, spec, *, policy: Policy | None,
                        objective: Objective, faults: FaultSpec | None,
                        sched_policy: str) -> SimResult:
        """FTL-translating adapter for :meth:`run_stream`: a generator
        turns each host ``RequestStream`` chunk into a placed
        ``OpTrace`` chunk — translation state, placement-slot offset
        and the fault sampler all carry across chunks, so the chunked
        answer equals the one-shot ``run(SimRequest(ftl=...))`` stream
        op-for-op.  The fold itself is delegated to the FTL
        sub-session, whose 7-class table owns chunk validation and
        byte accounting."""
        if self.config is None:
            raise ValueError(
                "workload queries need a Simulator bound to an SSDConfig "
                "(the scheduler needs the channel/way geometry)")
        if _sched.policy_is_dynamic(sched_policy):
            raise ValueError(
                f"sched policy {sched_policy!r} is dynamic — streaming "
                "chunks lower offline at a carried slot offset; dynamic "
                "dispatch needs the one-shot run(SimRequest(ftl=...)) "
                "path")
        if faults is not None and (faults.hedge_fraction > 0.0
                                   or faults.prog_fail_prob > 0.0
                                   or faults.erase_fail_prob > 0.0):
            raise ValueError(
                "run_stream(ftl=...) prices per-op retry/jitter "
                "surcharges only — hedging and block-level program/"
                "erase failures rewrite the whole stream and need the "
                "one-shot run(SimRequest(ftl=...)) path")
        sess = self._ftl_session(spec)
        C, W = self.config.channels, self.config.ways
        carry: dict = {"state": None, "off": 0, "sampler": None,
                       "stats": None}
        if faults is not None and not faults.is_zero:
            carry["sampler"] = FaultSampler(faults, C, W, sess.table)

        def translated():
            for st in chunks:
                if st.n_requests == 0:
                    continue
                tr = _ftl_scan.translate_scan(
                    st, spec, state=carry["state"],
                    page_bytes=self._page_bytes())
                carry["state"] = tr.state
                carry["stats"] = tr.stats
                ot, carry["off"] = _sched.lower_ops_chunk(
                    tr.op_cls, tr.arrival_us, C, W, sched_policy,
                    tr.payload, carry["off"])
                if carry["sampler"] is not None:
                    cls_view = np.where(
                        np.isin(tr.op_cls, (_ftl.FTL_READ, _ftl.GC_READ)),
                        _trace.READ, _trace.WRITE).astype(np.int32)
                    extra, _, _ = carry["sampler"].sample(cls_view)
                    ot = dataclasses.replace(
                        ot, extra_us=np.asarray(extra, np.float32))
                yield ot

        try:
            res = sess.run_stream(translated(), policy=policy,
                                  objective=objective)
        except ValueError:
            if carry["stats"] is None:     # no chunk carried a request
                raise ValueError(
                    "empty workload: no requests to translate") from None
            raise
        stats = carry["stats"]
        return dataclasses.replace(
            res, waf=stats.waf, gc_op_count=stats.gc_op_count,
            free_page_low_watermark=stats.free_page_low_watermark,
            ftl_stats=stats)

    def sweep(self, tables, trace, *,
              policy: Policy | None = None, engine: str = "prefix",
              segment_len: int | None = 64, combine: str = "chain",
              shard: bool | None = None, ftl=None,
              sched_policy: str = "stripe") -> np.ndarray:
        """[B] completion times of one trace under a batch of
        design-point tables (``tables=None`` sweeps the bound table
        alone) — the design-space fan-out direction of the serving
        path.  With more than one device the table batch shards across
        devices via ``jax.shard_map`` (``shard=None`` auto / ``True``;
        ``False`` forces the vmap path).

        ``ftl=`` switches to the *aged* design-space direction
        (DESIGN.md §2.11): ``trace`` is then a host
        :class:`RequestStream` and ``ftl`` a sequence of
        :class:`FTLSpec` design points sharing one geometry and timing
        — each point runs the whole translate→lower→simulate chain as
        one fused scan fold (preconditioning included), vmapped across
        points and sharded over devices like every other sweep.
        ``tables`` must be None (the FTL spec owns the 7-class table)
        and ``engine``/``segment_len``/``combine`` are ignored — the
        fused chain is the masked scan fold by construction."""
        self._calls += 1
        if ftl is not None:
            if tables is not None:
                raise ValueError(
                    "sweep(ftl=...) sweeps FTL design points — the "
                    "7-class table comes from the spec; tables must be "
                    "None")
            ftl = list(ftl)
            with _spans.span("sweep", call=self._calls,
                             ops=trace.n_requests, points=len(ftl)):
                return self._sweep_ftl(
                    trace, ftl, policy=policy or self.default_policy,
                    sched_policy=sched_policy, shard=shard)
        tables = [self.table] if tables is None else list(tables)
        with _spans.span("sweep", call=self._calls, ops=trace.n_ops,
                         points=len(tables)):
            return sweep_tables(
                tables, trace, policy=policy or self.default_policy,
                engine=engine, segment_len=segment_len, combine=combine,
                shard=shard)

    def _sweep_ftl(self, stream: RequestStream, specs, *, policy: Policy,
                   sched_policy: str, shard: bool | None) -> np.ndarray:
        """Fused aged sweep: precondition fold → window reset →
        translation fold → compaction → closed-form static lowering →
        masked end-time fold, with the batch of FTL design points
        riding vmap (plus ``shard_map`` with >1 device).  Exactness
        leans on two §2.11 invariants: the scan translator is op-for-op
        the host translator, and the closed-form slot/parity lowering
        is field-for-field ``lower_ops`` — so each lane's end time is
        the same chain the per-point ``run(SimRequest(ftl=...))`` path
        computes.

        Two memos make repeated sweeps cheap where the per-call host
        path cannot be: the *preconditioned state* is a pure function
        of the spec batch, so it folds once and is reused across calls
        (``_ftl_pre_states`` — the host translator re-ages on every
        call by design), and the row/op counts observed on a
        successful sweep are remembered per (specs, stream) so warm
        sweeps run exactly-sized buffers with no grow-and-retry replay
        (``_ftl_sweep_sizes``).  Emission rows compact into the op
        bucket through a searchsorted gather (XLA:CPU pays scatter
        cost per update row while gathers vectorise), so the masked
        end-time fold runs over ``t2 ≈ n_ops`` lanes instead of the
        raw ``t_max * (2*ppb+1)`` emission buffer."""
        if self.config is None:
            raise ValueError(
                "workload queries need a Simulator bound to an SSDConfig "
                "(the scheduler needs the channel/way geometry)")
        specs = list(specs)
        if not specs:
            raise ValueError("sweep(ftl=...) needs at least one FTLSpec")
        if stream.n_requests == 0:
            raise ValueError("empty workload: no requests to translate")
        g0 = (specs[0].blocks, specs[0].pages_per_block,
              float(specs[0].map_us), specs[0].erase_us)
        for s in specs[1:]:
            if (s.blocks, s.pages_per_block, float(s.map_us),
                    s.erase_us) != g0:
                raise ValueError(
                    "sweep(ftl=...) points must share geometry and "
                    "timing (blocks, pages_per_block, map_us, erase_us) "
                    "— vary overprovision / gc_policy / gc_free_blocks / "
                    "precondition per point")
        if _sched.policy_is_dynamic(sched_policy):
            raise ValueError(
                f"sched policy {sched_policy!r} is dynamic — the fused "
                "FTL sweep lowers placement in closed form; use "
                "run(SimRequest(ftl=...)) per point")
        batched = policy_is_batched(policy)
        sess = self._ftl_session(specs[0])
        blocks, ppb = specs[0].blocks, specs[0].pages_per_block
        C, W = self.config.channels, self.config.ways
        if stream.sectored:
            raise ValueError(
                "sweep(ftl=...) takes page-addressed streams; a "
                "sector-addressed one runs through run(SimRequest(ftl=...)) "
                "per point")
        cls, arr, rid, pay = _workload.request_ops(stream)
        if int(np.max(stream.op_cls)) > _trace.WRITE:
            raise ValueError(
                "FTL translation consumes host READ/WRITE streams only "
                f"(got op class {int(np.max(stream.op_cls))})")
        n = len(cls)
        n_b = _ftl_scan._bucket(n + ppb)   # burst-window slack
        dpad = n_b - n
        cls_p = jnp.asarray(np.pad(cls, (0, dpad)), jnp.int32)
        arr_p = jnp.asarray(np.pad(arr, (0, dpad)), jnp.float32)
        pay_p = jnp.asarray(np.pad(pay, (0, dpad)), bool)
        rid_p = jnp.asarray(np.pad(rid, (0, dpad)), jnp.int32)
        lpn_rows = np.stack([
            np.pad(_workload.request_lpns(stream, s.logical_pages),
                   (0, dpad)).astype(np.int32) for s in specs])
        pre_lists = [(_ftl.precondition_lpns(s) if s.precondition
                      else np.zeros(0, np.int64)) for s in specs]
        has_pre = any(len(p) for p in pre_lists)
        p_b = _ftl_scan._bucket(max(len(p) for p in pre_lists) + ppb,
                                floor=1) if has_pre else 1
        pre_rows = np.stack([
            np.pad(p, (0, p_b - len(p))).astype(np.int32)
            for p in pre_lists])
        pre_n = np.asarray([len(p) for p in pre_lists], np.int32)
        gc_free = np.asarray([s.gc_free_blocks for s in specs], np.int32)
        is_lru = np.asarray([s.gc_policy == "lru" for s in specs], bool)
        n_w = int(np.sum(cls == _trace.WRITE))
        mesh = _points_mesh() if shard is not False else None
        mesh_sz = None if mesh is None else mesh.devices.size
        S = 2 * ppb + 1

        # ---- stage 1: preconditioned states (a pure function of the
        # spec batch — fold once, reuse across calls)
        skey = (tuple(specs), mesh_sz)
        st0 = self._ftl_pre_states.get(skey)
        if st0 is None and not has_pre:
            fs0 = _ftl_scan.scan_state_fresh(specs[0])
            st0 = jax.tree.map(
                lambda x: jnp.broadcast_to(
                    jnp.asarray(x),
                    (len(specs),) + jnp.shape(jnp.asarray(x))), fs0)
        elif st0 is None:
            t_pre = max(_ftl_scan.estimate_t_max(s, 0, len(p),
                                                 precondition=True)
                        for s, p in zip(specs, pre_lists) if len(p))

            def build_pre(t_pre):
                fold_p = _ftl_scan.make_translate_fold(blocks, ppb,
                                                       p_b, t_pre)
                fs0 = _ftl_scan.scan_state_fresh(specs[0])
                cls_pre = jnp.full((p_b,), _trace.WRITE, jnp.int32)
                arr_pre = jnp.zeros((p_b,), jnp.float32)
                pay_pre = jnp.zeros((p_b,), bool)
                rid_pre = jnp.full((p_b,), -1, jnp.int32)

                def point(pre_lpn, n_pre, gfree, lru):
                    fs, _ = fold_p(cls_pre, arr_pre, pay_pre, rid_pre,
                                   pre_lpn, n_pre, gfree, lru, fs0)
                    pre_done = ((fs.h >= n_pre)
                                & (fs.mode == _ftl_scan.MODE_HOST))
                    return _ftl_scan._reset_window(fs, ppb), pre_done

                vm = jax.vmap(point)
                if mesh is not None:
                    return _shard_points(mesh, vm, n_sharded=4)
                return jax.jit(vm)

            while True:
                fn = self._closure(
                    ("ftl-sweep-pre", blocks, ppb, p_b, t_pre, mesh_sz),
                    functools.partial(build_pre, t_pre))
                st0, pre_done = fn(pre_rows, pre_n, gc_free, is_lru)
                err = np.asarray(st0.err)
                if err.any():
                    i = int(np.flatnonzero(err)[0])
                    _ftl_scan._raise_scan_error(int(err[i]), specs[i])
                if np.asarray(pre_done).all():
                    break
                t_pre *= 2
        self._ftl_pre_states[skey] = st0
        self._ftl_pre_states.move_to_end(skey)
        while len(self._ftl_pre_states) > 4:
            self._ftl_pre_states.popitem(last=False)

        # ---- stage 2: translate → compact → lower → simulate, with
        # learned buffer sizes per (specs, stream)
        digest = hashlib.blake2b(digest_size=8)
        for a in (cls, arr, lpn_rows):
            digest.update(np.ascontiguousarray(a).tobytes())
        wkey = (tuple(specs), n, n_w, digest.hexdigest(), sched_policy,
                batched, mesh_sz)
        sizes = self._ftl_sweep_sizes.get(wkey)
        if sizes is not None:
            t_max, t2 = sizes
            self._ftl_sweep_sizes.move_to_end(wkey)
        else:
            t_max = max(_ftl_scan.estimate_t_max(s, n - n_w, n_w)
                        for s in specs)
            t2 = _ftl_scan._bucket(
                max(_ftl_scan.estimate_ops(s, n - n_w, n_w)
                    for s in specs))

        def build(t_max, t2):
            fold_m = _ftl_scan.make_translate_fold(blocks, ppb, n_b,
                                                   t_max)
            T = t_max * S
            slot1 = jnp.arange(1, t2 + 1, dtype=jnp.int32)
            # compacted op i sits at slot i, so the closed-form static
            # placement (`lower_ops` field-for-field) is a closure
            # constant shared by every design point
            slot = jnp.arange(t2, dtype=jnp.int32)
            if sched_policy == "stripe":
                chan_c, way_c = slot % C, (slot // C) % W
            else:                       # "round_robin": way-first
                way_c, chan_c = slot % W, (slot // W) % C
            par_c = (slot // (C * W)) % 2
            extra_c = jnp.zeros((t2,), jnp.float32)

            def point(fs, lpn, gfree, lru,
                      h_cls, h_arr, h_pay, h_rid, n_eff):
                fs, ys = fold_m(h_cls, h_arr, h_pay, h_rid, lpn, n_eff,
                                gfree, lru, fs)
                # compact the [t_max, 2*ppb+1] emission rows into the
                # op bucket: position of the i-th valid lane via binary
                # search on the running popcount (gathers, no scatter)
                op_cls, arrival, valid = (ys[0].reshape(-1),
                                          ys[1].reshape(-1),
                                          ys[4].reshape(-1))
                cum = jnp.cumsum(valid.astype(jnp.int32))
                n_ops = cum[-1]
                pos = jnp.minimum(
                    jnp.searchsorted(cum, slot1, side="left"), T - 1)
                end = _sim._trace_end_time_masked_impl(
                    *sess._targs, op_cls[pos], chan_c, way_c, par_c,
                    arrival[pos], extra_c, slot1 <= n_ops, C, batched)
                done = ((fs.h >= n_eff)
                        & (fs.mode == _ftl_scan.MODE_HOST))
                rows = jnp.sum(jnp.any(ys[4], axis=1).astype(jnp.int32))
                return end, fs.err, done, n_ops, rows

            vm = jax.vmap(point, in_axes=(0, 0, 0, 0,
                                          None, None, None, None, None))
            if mesh is not None:
                return _shard_points(mesh, vm, n_sharded=4)
            return jax.jit(vm)

        while True:
            fn = self._closure(
                ("ftl-sweep", blocks, ppb, n_b, t_max, t2, C, W,
                 sched_policy, batched, mesh_sz),
                functools.partial(build, t_max, t2))
            end, err, done, n_ops, rows = fn(
                st0, lpn_rows, gc_free, is_lru,
                cls_p, arr_p, pay_p, rid_p, jnp.int32(n))
            err = np.asarray(err)
            if err.any():
                i = int(np.flatnonzero(err)[0])
                _ftl_scan._raise_scan_error(int(err[i]), specs[i])
            n_ops = np.asarray(n_ops)
            grow = False
            if not np.asarray(done).all():
                t_max *= 2           # emission buffer overflowed
                grow = True
            if int(n_ops.max()) > t2:
                # op bucket overflowed; n_ops from an overflowed
                # emission buffer is a lower bound, which only means
                # one more growth round
                t2 = _ftl_scan._bucket(int(n_ops.max()))
                grow = True
            if not grow:
                self._ftl_sweep_sizes[wkey] = (
                    _ftl_scan._bucket(int(np.asarray(rows).max()) + 1),
                    t2)
                while len(self._ftl_sweep_sizes) > 32:
                    self._ftl_sweep_sizes.popitem(last=False)
                return np.asarray(end, np.float64)


@functools.lru_cache(maxsize=128)
def simulator_for(config: SSDConfig) -> Simulator:
    """Memoised :class:`Simulator` per design point (``SSDConfig`` is a
    frozen dataclass, so it is the cache key)."""
    return Simulator(config)


# ---------------------------------------------------------------------------
# Module-level query functions (what the deprecated shims delegate to)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=1)
def _points_mesh():
    """Process-wide 1-D ``("points",)`` sweep mesh over every device;
    None with a single device, which drops every sharded entry point
    back to its plain vmap path."""
    from repro.launch.mesh import make_points_mesh
    return make_points_mesh()


def _shard_points(mesh, fn, *, n_sharded: int):
    from repro.launch.mesh import shard_points
    return shard_points(mesh, fn, n_sharded=n_sharded)


@functools.lru_cache(maxsize=64)
def _sharded_batch_fn(mesh, engine: str, n_channels: int, n_ways: int,
                      batched: bool, segment_len, combine):
    """Memoised shard_map wrapper for the table-batched sweep folds:
    the 7 stacked table columns shard their leading (design-point) axis
    over the mesh, the trace arrays replicate — repeated sweeps over the
    same geometry reuse one compiled sharded program."""
    if engine == "scan":
        fn = functools.partial(_sim.trace_end_time_batch,
                               n_channels=n_channels, batched=batched)
    else:
        fn = functools.partial(
            _sim.trace_end_time_prefix_batch, n_channels=n_channels,
            n_ways=n_ways, batched=batched, segment_len=segment_len,
            combine=combine)
    return _shard_points(mesh, fn, n_sharded=7)


def sweep_tables(tables, trace: OpTrace, *, policy: Policy = "eager",
                 engine: str = "prefix", segment_len: int | None = 64,
                 combine: str = "chain",
                 shard: bool | None = None) -> np.ndarray:
    """[B] completion times (us) of one trace under a batch of
    design-point tables, dispatched through the registry.  With more
    than one device the stacked tables shard across devices via
    ``jax.shard_map`` (scan/prefix engines; the batch pads to a device
    multiple and slices back); ``shard=False`` forces the vmap path,
    one device always falls back to it."""
    batched = policy_is_batched(policy)
    eng = get_engine(engine)
    if trace.n_ops == 0:
        raise ValueError("empty trace: no ops to simulate")
    tables = list(tables)
    mesh = _points_mesh() if shard is not False else None
    if (mesh is not None and len(tables) > 1 and engine in ("scan", "prefix")
            and eng.caps.jittable and eng.caps.batched_tables):
        fn = _sharded_batch_fn(mesh, engine, trace.channels, trace.ways,
                               batched, segment_len, combine)
        args = _staged_batch_args(tables, trace)
        with _spans.span("fold", engine=engine, ops=trace.n_ops,
                         steps=trace.n_ops, points=len(tables)):
            return np.asarray(fn(*args))
    return eng.end_time_batch(tables, trace, batched=batched,
                              segment_len=segment_len, combine=combine)


@functools.lru_cache(maxsize=256)
def _steady_trace_cached(n_pages: int, channels: int, ways: int,
                         op_cls: int) -> OpTrace:
    return _trace.steady_trace(n_pages, channels, ways, op_cls)


def steady_bandwidth_mb_s(cfg: SSDConfig, mode: str,
                          n_pages: int = 512) -> float:
    """SSD-level steady-stream bandwidth (MB/s): all channels simulated
    jointly against the shared controller, capped by the SATA host link.
    ``n_pages`` is per channel.  (The session-API home of the old
    ``sim.ssd_bandwidth_mb_s``.)"""
    if mode not in ("read", "write"):
        raise ValueError(f"unknown mode {mode!r} (one of 'read', 'write')")
    trace = _steady_trace_cached(
        n_pages, cfg.channels, cfg.ways,
        _trace.READ if mode == "read" else _trace.WRITE)
    res = Simulator.for_config(cfg).run(trace, policy=cfg.policy)
    return float(min(res.mb_s, cfg.sata_mb_s))


def steady_channel_bandwidth_mb_s(op: PageOpParams, ways,
                                  policy: Policy = "eager",
                                  n_pages: int = 512,
                                  engine: str = "scan") -> jax.Array:
    """Steady-stream bandwidth of a single channel (MB/s) for one
    op-class design point, via any engine with the homogeneous-pattern
    capability (scan / prefix / squaring)."""
    batched = policy_is_batched(policy)
    end = get_engine(engine).steady_channel_end(
        op, ways, n_pages=n_pages, batched=batched)
    return (n_pages * op.data_bytes) / end


@functools.lru_cache(maxsize=64)
def _sharded_sweep_steady_fn(mesh, engine: str, n_pages: int,
                             batched: bool):
    """Memoised shard_map wrapper for the homogeneous design-point
    sweep: all 8 per-point arrays shard their leading axis."""
    base = (_sim._sweep_scan_jit if engine == "scan"
            else _sim._sweep_squaring_jit)
    fn = functools.partial(base, n_pages=n_pages, batched=batched)
    return _shard_points(mesh, fn, n_sharded=8)


def sweep_steady_bandwidth_mb_s(cmd_us, pre_us, slot_us, post_lo_us,
                                post_hi_us, ctrl_us, data_bytes, ways,
                                n_pages: int = 512, batched: bool = False,
                                engine: str = "scan",
                                shard: bool | None = None) -> jax.Array:
    """Vectorised single-channel steady bandwidth over design points
    (arrays [N]), via an engine with the sweep capability
    (scan / squaring).  With more than one device the design points
    shard across devices via ``jax.shard_map`` (``shard=False`` forces
    the vmap path) — the fan-out the ``calibrate`` fitting grids ride."""
    scalars = (cmd_us, pre_us, slot_us, post_lo_us, post_hi_us, ctrl_us)
    mesh = _points_mesh() if shard is not False else None
    if mesh is not None and engine in ("scan", "squaring"):
        if engine == "squaring":
            _sim._validate_squaring_ways(ways)
        args = tuple(jnp.asarray(x) for x in scalars + (data_bytes, ways))
        if args[0].ndim == 1 and int(args[0].shape[0]) > 1:
            fn = _sharded_sweep_steady_fn(mesh, engine, n_pages, batched)
            return fn(*args)
    return get_engine(engine).sweep_steady(
        scalars, data_bytes, ways, n_pages=n_pages, batched=batched)


__all__ = [
    "CacheInfo", "CapabilityError", "Engine", "EngineCaps", "FaultSpec",
    "OBJECTIVES", "Objective", "Policy", "RequestStream", "SimRequest",
    "SimResult", "Simulator", "engine_capabilities", "get_engine",
    "register_engine", "registered_engines", "simulator_for",
    "steady_bandwidth_mb_s", "steady_channel_bandwidth_mb_s",
    "sweep_steady_bandwidth_mb_s", "sweep_tables",
]
