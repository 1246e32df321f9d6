"""JAX-native discrete-event simulator of a multi-channel SSD.

The paper evaluates its DDR NAND interface with a behavioural RTL
co-simulation (MentorGraphics Seamless).  We reformulate that event loop as
a **data-parallel timeline recurrence**: the only state needed to advance
the simulation by one page operation is

    s = (bus_free[ch_0..ch_{C-1}],
         chip_free[ch, way_0..way_{W-1}],
         ctrl_free,                       # shared ECC/FTL controller
         round_start[ch])

and the per-op update is a (max, +) expression over that state.  Each op in
a trace carries (op-class, channel, way, page-parity); the per-op timing is
a gather from a small op-class table (``repro.core.trace.OpClassTable``),
so a single engine handles heterogeneous mixed read/write traffic across
all channels jointly.  Interchangeable engines evaluate the recurrence
(DESIGN.md §2):

* ``trace_end_time`` — ``jax.lax.scan`` over trace ops (jit/vmap-able,
  O(T) depth; ``trace_end_time_masked[_many]`` are the padded-bucket
  variants the ``repro.core.api`` session cache serves from);
* ``trace_end_time_prefix`` — the log-depth engine: per-op (max,+) step
  matrices built in-trace (``repro.core.maxplus_form``) and folded with
  a segmented parallel prefix, O(L + log T) depth (DESIGN.md §2.3);
* ``_squaring_end_time`` — homogeneous streams fold one period and
  reach ``n_pages`` by repeated (max,+) matrix squaring, O(log n_pages);
* ``repro.kernels.maxplus`` — the same recurrence as a blocked (max,+)
  matrix fold in Pallas, gathering the per-op-class matrix ``A[idx[t]]``
  per step (TPU-native, batched across design points; also exposes the
  segmented and squaring strategies);
* ``repro.core.sim_ref`` — plain-Python trace oracle for tests.

Every entry point is **arrival-aware** (DESIGN.md §2.6): the per-op
``arrival_us`` operand lower-bounds the ready time (zero = the old
back-to-back behaviour, bit-for-bit).  ``trace_completions`` emits
per-op completion times for request-latency percentiles, and
``dispatch_trace`` is the joint dispatch+simulate fold behind the
dynamic scheduling policies of ``repro.core.sched``.

All engine *dispatch* lives in ``repro.core.api`` (the registry behind
the ``Simulator`` session, DESIGN.md §2.5); this module holds only the
jit-compiled evaluation primitives.  The old query entry points
(``channel_bandwidth_mb_s`` / ``sweep_bandwidth_mb_s`` /
``ssd_bandwidth_mb_s``) survive below as deprecated delegating shims.

Every engine can also carry the phase-resolved energy accumulator of
``repro.core.energy`` alongside the end-time recurrence
(``trace_end_time_energy`` / ``trace_end_time_prefix_energy`` here, the
kernel fold in ``repro.kernels.maxplus``; DESIGN.md §2.4).

Model structure (C channels, W ways each, round-robin page striping)
--------------------------------------------------------------------
READ  page:  pre = t_CMD + t_R   (off-bus: command latch + array fetch)
             slot = t_DATA(page+spare) + t_ECC   (bus + ECC occupancy)
WRITE page:  slot = t_CMD + t_DATA + t_ECC + W*t_POLL  (the controller
             status-polls every way once per page slot), then the chip is
             busy for t_PROG.  MLC chips program paired pages with strongly
             asymmetric times (lower/upper page); the trace carries the
             page parity explicitly — it is what makes MLC write
             interleaving scale sub-ideally (paper §5.3.1 Case III).

Shared-controller occupancy (DESIGN.md §3)
------------------------------------------
The paper's SSD has ONE embedded controller arbitrating all channels,
while every channel carries its own NAND_IF + ECC hardware (§2.2.1).  Per
op, the clock-independent FTL/firmware share of the slot (``ctrl_us`` =
ECC fixed cost + write status polling) occupies that controller serially
across channels (``ctrl_free`` state row).  With more than one active
channel the firmware additionally pays, per bus grant, a context switch
plus a status scan of every other channel —
``arb_us = (CTRL_ARB_SWITCH_FRAC + CTRL_ARB_SCAN_FRAC*(C-1)) * ctrl_us``
(zero for a dedicated single-channel loop).  This replaces the retired
``STRIPE_EFFICIENCY_EXP`` bandwidth fudge: multi-channel Table 4 numbers
now come out of the joint simulation itself, and the old exponent survives
only as a calibration cross-check (``repro.core.calibrate``).

Scheduling policies
-------------------
The paper does not publish its firmware arbitration rules, which matter at
intermediate way counts (DESIGN.md §5).  Two documented policies bound the
behaviour:

* ``eager``   — a chip's next command is (re)issued as soon as the chip is
  idle (commands squeeze into bus gaps; 7 cycles ≈ 0.1 us vs transfers of
  12–90 us).
* ``batched`` — strict in-order firmware loop: round r's commands are only
  issued once the channel's bus drained round r-1's transfers.

Reads bracket the paper's measurements between these; writes are bus-gated
in both, so the policies coincide for writes.

Units: microseconds / bytes / MB-per-second (1 MB = 1e6 bytes, as in the
paper).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import warnings
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

# make_interface / nand_chip are no longer used here since the query
# entry points moved to repro.core.api, but stay as deliberate
# re-exports (long-standing import site for tests and callers).
from repro.core.interface import (WRITE_POLL_FIXED_US, InterfaceKind,  # noqa: F401
                                  InterfaceParams, make_interface)
from repro.core.nand import CellType, NandChipParams, chip as nand_chip  # noqa: F401

MAX_WAYS = 16
MAX_CHANNELS = 8

# Firmware channel arbitration: with more than one active channel, each
# bus grant costs the single controller thread a context switch
# (CTRL_ARB_SWITCH_FRAC of the op's firmware occupancy) plus a status
# scan of every additional channel (CTRL_ARB_SCAN_FRAC each).  A
# dedicated single-channel loop pays neither.  Both fractions are
# calibrated on paper Table 4 (constant-capacity channel/way trade-off);
# see DESIGN.md §3.2 and ``repro.core.calibrate.stripe_crosscheck``.
CTRL_ARB_SWITCH_FRAC = 0.4
CTRL_ARB_SCAN_FRAC = 0.1

Policy = Literal["eager", "batched"]
Mode = Literal["read", "write"]
# evaluation strategy for the (identical) recurrence; the authoritative
# set is the repro.core.api registry — this literal mirrors it for the
# deprecated shim signatures
Engine = Literal["scan", "prefix", "squaring", "pallas", "oracle"]

POLICIES: tuple[str, ...] = ("eager", "batched")


def policy_is_batched(policy: str) -> bool:
    """Validate the ``Policy`` literal once and return its batched-ness.

    Every dispatch layer used to compare ``policy == "batched"`` ad hoc,
    so a typo like ``"bathced"`` silently simulated ``"eager"``; this is
    the single place that comparison is allowed to happen."""
    if policy not in POLICIES:
        raise ValueError(f"unknown policy {policy!r} "
                         f"(one of {', '.join(map(repr, POLICIES))})")
    return policy == "batched"


def controller_arb_us(ctrl_us: float, channels: int) -> float:
    """Per-op firmware arbitration charge for a C-channel controller."""
    if channels <= 1:
        return 0.0
    return (CTRL_ARB_SWITCH_FRAC
            + CTRL_ARB_SCAN_FRAC * (channels - 1)) * ctrl_us


@dataclasses.dataclass(frozen=True)
class SSDConfig:
    """One SSD design point (paper §5.3 axes)."""

    interface: InterfaceKind = InterfaceKind.PROPOSED
    cell: CellType = CellType.SLC
    channels: int = 1
    ways: int = 1
    policy: Policy = "eager"
    sata_mb_s: float = 300.0  # SATA2 ("SATA 3 Gbit/s"), paper footnote 1

    def __post_init__(self):
        policy_is_batched(self.policy)   # reject typos at construction

    def describe(self) -> str:
        return (
            f"{self.interface.value}/{self.cell.value}"
            f" {self.channels}ch x {self.ways}way [{self.policy}]"
        )


@dataclasses.dataclass(frozen=True)
class PageOpParams:
    """Scalar timing of one page-operation class.

    Recurrence consumed by all engines, per op on channel c / way w (see
    module docstring; arb_us = controller_arb_us(ctrl_us, C)):

        ready          = chip_free[c,w] + cmd_us + pre_us           (eager)
                         round_start[c] + (w+1)*cmd_us + pre_us     (batched)
        start          = max(bus_free[c], ready, ctrl_free) + arb_us
        bus_free'[c]   = start + slot_us
        ctrl_free'     = start + ctrl_us
        chip_free'[c,w]= bus_free'[c] + post_us(page parity)
    """

    cmd_us: float        # command/address latch occupancy
    pre_us: float        # off-bus latency after cmd (t_R for reads, 0 writes)
    slot_us: float       # bus+controller occupancy (data burst + ECC [+ polls])
    post_lo_us: float    # chip busy after slot (t_PROG; 0 for reads)
    post_hi_us: float    # odd-numbered page on a chip (MLC upper page)
    data_bytes: int      # user payload per op
    ctrl_us: float = 0.0  # FTL/firmware share of slot_us (shared controller)
    io_us: float = 0.0   # bus data-burst share of slot_us (energy phase split)

    def post_mean_us(self) -> float:
        return 0.5 * (self.post_lo_us + self.post_hi_us)


def page_op_params(
    iface: InterfaceParams, nand: NandChipParams, mode: Mode, ways: int
) -> PageOpParams:
    io_us = iface.data_us(nand.page_total_bytes)
    if mode == "read":
        return PageOpParams(
            cmd_us=iface.cmd_us,
            pre_us=nand.t_r_us,
            slot_us=io_us + iface.ecc_us(nand.cell),
            post_lo_us=0.0,
            post_hi_us=0.0,
            data_bytes=nand.page_data_bytes,
            ctrl_us=iface.ecc_fixed_us(nand.cell),
            io_us=io_us,
        )
    poll_us = (ways * nand.t_poll_cycles * iface.cycle_ns * 1e-3
               + WRITE_POLL_FIXED_US)
    return PageOpParams(
        cmd_us=iface.cmd_us,
        pre_us=0.0,
        slot_us=io_us + iface.ecc_us(nand.cell) + poll_us,
        post_lo_us=nand.t_prog_lo_us,
        post_hi_us=nand.t_prog_hi_us,
        data_bytes=nand.page_data_bytes,
        ctrl_us=iface.ecc_fixed_us(nand.cell) + poll_us,
        io_us=io_us,
    )


# ---------------------------------------------------------------------------
# lax.scan trace engine
# ---------------------------------------------------------------------------


def _trace_step_fn(cmd_us, pre_us, slot_us, post_lo_us, post_hi_us,
                   ctrl_us, arb_us, batched):
    """Single per-op state update — the one recurrence every scan-engine
    entry point (plain and energy-carrying) folds.  The op tuple carries
    the request arrival time: the ready base is maxed with it before the
    command-issue offset, so an op can never start before its request
    arrives (arrival 0 = the old back-to-back behaviour, bit-for-bit).
    It also carries the op's reliability surcharge ``ext`` (read-retry +
    jitter time sampled outside the fold, DESIGN.md §2.8): each retry
    re-runs the *sense* inside the die, so ``ext`` extends the op's chip
    occupancy (its release, and hence its completion) — never the
    channel bus and never the serial controller.  A retry storm
    therefore delays its own request and later ops on the *same chip*,
    but cannot head-of-line-block the channel or the FCFS issue stage —
    which is exactly what lets a hedged duplicate on another chip
    overtake it.  Adding 0.0 is exact in float32, so a fault-free
    vector reproduces the old state bit-for-bit."""

    def step(state, op):
        bus_free, chip_free, ctrl_free, round_start = state
        k, c, w, par, arr, ext = op
        cmd = cmd_us[k]
        round_start = jnp.where(
            w == 0, round_start.at[c].set(bus_free[c]), round_start)
        if batched:
            base = jnp.maximum(round_start[c], arr)
            ready = base + (w + 1).astype(jnp.float32) * cmd + pre_us[k]
        else:
            base = jnp.maximum(chip_free[c, w], arr)
            ready = base + cmd + pre_us[k]
        start = (jnp.maximum(jnp.maximum(bus_free[c], ready), ctrl_free)
                 + arb_us[k])
        new_bus = start + slot_us[k]
        post = jnp.where(par % 2 == 0, post_lo_us[k], post_hi_us[k])
        bus_free = bus_free.at[c].set(new_bus)
        chip_free = chip_free.at[c, w].set(new_bus + post + ext)
        return (bus_free, chip_free, start + ctrl_us[k], round_start)

    return step


def _trace_scan_init(n_channels):
    return (
        jnp.zeros((n_channels,), jnp.float32),
        jnp.zeros((n_channels, MAX_WAYS), jnp.float32),
        jnp.asarray(0.0, jnp.float32),
        jnp.zeros((n_channels,), jnp.float32),
    )


def _trace_ops(cls, channel, way, parity, arrival, extra):
    return (cls.astype(jnp.int32), channel.astype(jnp.int32),
            way.astype(jnp.int32), parity.astype(jnp.int32),
            arrival.astype(jnp.float32), extra.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("n_channels", "batched"))
def trace_end_time(
    cmd_us: jax.Array,       # [K] op-class timing table
    pre_us: jax.Array,       # [K]
    slot_us: jax.Array,      # [K]
    post_lo_us: jax.Array,   # [K]
    post_hi_us: jax.Array,   # [K]
    ctrl_us: jax.Array,      # [K] shared-controller share of slot
    arb_us: jax.Array,       # [K] per-op firmware arbitration charge
    cls: jax.Array,          # [T] int32 op-class index per op
    channel: jax.Array,      # [T] int32
    way: jax.Array,          # [T] int32
    parity: jax.Array,       # [T] int32 page parity (MLC lower/upper)
    arrival_us: jax.Array,   # [T] float32 request arrival per op (0 = t0)
    extra_us: jax.Array,     # [T] float32 reliability surcharge (0 = none)
    n_channels: int,
    batched: bool,
) -> jax.Array:
    """Completion time (us) of a heterogeneous op trace on C channels."""
    upd = _trace_step_fn(cmd_us, pre_us, slot_us, post_lo_us, post_hi_us,
                         ctrl_us, arb_us, batched)
    (bus_free, chip_free, _, _), _ = jax.lax.scan(
        lambda s, op: (upd(s, op), None), _trace_scan_init(n_channels),
        _trace_ops(cls, channel, way, parity, arrival_us, extra_us))
    return jnp.maximum(jnp.max(bus_free), jnp.max(chip_free))


@functools.partial(jax.jit, static_argnames=("n_channels", "batched"))
def trace_end_time_energy(
    cmd_us: jax.Array,       # [K] op-class timing table
    pre_us: jax.Array,       # [K]
    slot_us: jax.Array,      # [K]
    post_lo_us: jax.Array,   # [K]
    post_hi_us: jax.Array,   # [K]
    ctrl_us: jax.Array,      # [K]
    arb_us: jax.Array,       # [K]
    e_op_uj: jax.Array,      # [K, 2, P] per-op phase energies (parity axis)
    cls: jax.Array,          # [T]
    channel: jax.Array,      # [T]
    way: jax.Array,          # [T]
    parity: jax.Array,       # [T]
    arrival_us: jax.Array,   # [T]
    extra_us: jax.Array,     # [T]
    n_channels: int,
    batched: bool,
) -> tuple[jax.Array, jax.Array]:
    """(end_us, [P] phase-energy sums in uJ): the same recurrence as
    ``trace_end_time`` carrying a phase-energy accumulator per op
    (DESIGN.md §2.4) — one fused scan, no second pass over the trace."""
    upd = _trace_step_fn(cmd_us, pre_us, slot_us, post_lo_us, post_hi_us,
                         ctrl_us, arb_us, batched)

    def step(carry, op):
        state, acc = carry
        k, c, w, par, arr, ext = op
        return (upd(state, op), acc + e_op_uj[k, par % 2]), None

    init = (_trace_scan_init(n_channels),
            jnp.zeros((e_op_uj.shape[-1],), jnp.float32))
    ((bus_free, chip_free, _, _), acc), _ = jax.lax.scan(
        step, init,
        _trace_ops(cls, channel, way, parity, arrival_us, extra_us))
    return jnp.maximum(jnp.max(bus_free), jnp.max(chip_free)), acc


def _trace_end_time_masked_impl(
        cmd_us, pre_us, slot_us, post_lo_us, post_hi_us, ctrl_us, arb_us,
        cls, channel, way, parity, arrival, extra, valid, n_channels,
        batched):
    upd = _trace_step_fn(cmd_us, pre_us, slot_us, post_lo_us, post_hi_us,
                         ctrl_us, arb_us, batched)

    def step(state, op):
        k, c, w, par, arr, ext, ok = op
        new = upd(state, (k, c, w, par, arr, ext))
        return jax.tree.map(lambda n, o: jnp.where(ok, n, o), new, state), None

    ops = _trace_ops(cls, channel, way, parity, arrival, extra) \
        + (valid.astype(bool),)
    (bus_free, chip_free, _, _), _ = jax.lax.scan(
        step, _trace_scan_init(n_channels), ops)
    return jnp.maximum(jnp.max(bus_free), jnp.max(chip_free))


@functools.partial(jax.jit, static_argnames=("n_channels", "batched"))
def trace_end_time_masked(
    cmd_us: jax.Array,       # [K] op-class timing table
    pre_us: jax.Array,       # [K]
    slot_us: jax.Array,      # [K]
    post_lo_us: jax.Array,   # [K]
    post_hi_us: jax.Array,   # [K]
    ctrl_us: jax.Array,      # [K]
    arb_us: jax.Array,       # [K]
    cls: jax.Array,          # [T] (T = padded length bucket)
    channel: jax.Array,      # [T]
    way: jax.Array,          # [T]
    parity: jax.Array,       # [T]
    arrival_us: jax.Array,   # [T]
    extra_us: jax.Array,     # [T]
    valid: jax.Array,        # [T] bool; False = padding (state no-op)
    n_channels: int,
    batched: bool,
) -> jax.Array:
    """``trace_end_time`` with a validity mask: invalid (padding) ops
    leave the carried state bitwise unchanged, so a trace padded to a
    power-of-two length bucket produces the *identical* end time while
    nearby trace lengths share one compiled program — the shape the
    ``repro.core.api`` session cache serves repeated queries from."""
    return _trace_end_time_masked_impl(
        cmd_us, pre_us, slot_us, post_lo_us, post_hi_us, ctrl_us, arb_us,
        cls, channel, way, parity, arrival_us, extra_us, valid, n_channels,
        batched)


@functools.partial(jax.jit, static_argnames=("n_channels", "batched"))
def trace_end_time_masked_many(
    cmd_us: jax.Array,       # [K] one op-class table shared by the batch
    pre_us: jax.Array,       # [K]
    slot_us: jax.Array,      # [K]
    post_lo_us: jax.Array,   # [K]
    post_hi_us: jax.Array,   # [K]
    ctrl_us: jax.Array,      # [K]
    arb_us: jax.Array,       # [K]
    cls: jax.Array,          # [B, T] a bucket of padded traces
    channel: jax.Array,      # [B, T]
    way: jax.Array,          # [B, T]
    parity: jax.Array,       # [B, T]
    arrival_us: jax.Array,   # [B, T]
    extra_us: jax.Array,     # [B, T]
    valid: jax.Array,        # [B, T]
    n_channels: int,
    batched: bool,
) -> jax.Array:
    """[B] completion times of a *bucket of traces* under one timing
    table — the packed serving path behind ``Simulator.run_many``:
    heterogeneous traces padded to a shared length bucket evaluate in
    one vmapped masked fold."""
    return jax.vmap(
        lambda a, b, c, d, e, x, v: _trace_end_time_masked_impl(
            cmd_us, pre_us, slot_us, post_lo_us, post_hi_us, ctrl_us,
            arb_us, a, b, c, d, e, x, v, n_channels, batched)
    )(cls, channel, way, parity, arrival_us, extra_us, valid)


@functools.partial(jax.jit, static_argnames=("n_channels", "batched"))
def trace_completions(
    cmd_us: jax.Array,       # [K] op-class timing table
    pre_us: jax.Array,       # [K]
    slot_us: jax.Array,      # [K]
    post_lo_us: jax.Array,   # [K]
    post_hi_us: jax.Array,   # [K]
    ctrl_us: jax.Array,      # [K]
    arb_us: jax.Array,       # [K]
    cls: jax.Array,          # [T]
    channel: jax.Array,      # [T]
    way: jax.Array,          # [T]
    parity: jax.Array,       # [T]
    arrival_us: jax.Array,   # [T]
    extra_us: jax.Array,     # [T]
    n_channels: int,
    batched: bool,
) -> tuple[jax.Array, jax.Array]:
    """(end_us, [T] per-op completion times): the scan recurrence
    emitting each op's completion — bus drain for reads (data
    delivered), bus drain + t_PROG for writes (page durable).  This is
    the latency-extraction fold behind per-request p50/p99 on
    arrival-aware workloads (DESIGN.md §2.6); the end time is the same
    recurrence as ``trace_end_time``."""
    upd = _trace_step_fn(cmd_us, pre_us, slot_us, post_lo_us, post_hi_us,
                         ctrl_us, arb_us, batched)

    def step(state, op):
        new = upd(state, op)
        _, c, w, _, _, _ = op
        return new, new[1][c, w]                  # chip_free[c, w]

    (bus_free, chip_free, _, _), comp = jax.lax.scan(
        step, _trace_scan_init(n_channels),
        _trace_ops(cls, channel, way, parity, arrival_us, extra_us))
    return jnp.maximum(jnp.max(bus_free), jnp.max(chip_free)), comp


@functools.partial(jax.jit, static_argnames=("n_channels", "batched"))
def trace_completions_masked(
    cmd_us: jax.Array,       # [K] op-class timing table
    pre_us: jax.Array,       # [K]
    slot_us: jax.Array,      # [K]
    post_lo_us: jax.Array,   # [K]
    post_hi_us: jax.Array,   # [K]
    ctrl_us: jax.Array,      # [K]
    arb_us: jax.Array,       # [K]
    cls: jax.Array,          # [T] (T = padded length bucket)
    channel: jax.Array,      # [T]
    way: jax.Array,          # [T]
    parity: jax.Array,       # [T]
    arrival_us: jax.Array,   # [T]
    extra_us: jax.Array,     # [T]
    valid: jax.Array,        # [T] bool; False = padding (state no-op)
    n_channels: int,
    batched: bool,
) -> tuple[jax.Array, jax.Array]:
    """``trace_completions`` over a padded length bucket: padding ops
    leave the state bitwise unchanged and their emitted completions are
    trailing garbage the caller slices off — so workload latency
    queries share the same power-of-two compile buckets as the masked
    end-time fold instead of paying one XLA compile per trace length."""
    upd = _trace_step_fn(cmd_us, pre_us, slot_us, post_lo_us, post_hi_us,
                         ctrl_us, arb_us, batched)

    def step(state, op):
        k, c, w, par, arr, ext, ok = op
        new = upd(state, (k, c, w, par, arr, ext))
        new = jax.tree.map(lambda n, o: jnp.where(ok, n, o), new, state)
        return new, new[1][c, w]                  # chip_free[c, w]

    ops = _trace_ops(cls, channel, way, parity, arrival_us, extra_us) \
        + (valid.astype(bool),)
    (bus_free, chip_free, _, _), comp = jax.lax.scan(
        step, _trace_scan_init(n_channels), ops)
    return jnp.maximum(jnp.max(bus_free), jnp.max(chip_free)), comp


@functools.partial(jax.jit, static_argnames=("n_channels", "batched"))
def trace_chunk_fold(
    cmd_us: jax.Array,       # [K] op-class timing table
    pre_us: jax.Array,       # [K]
    slot_us: jax.Array,      # [K]
    post_lo_us: jax.Array,   # [K]
    post_hi_us: jax.Array,   # [K]
    ctrl_us: jax.Array,      # [K]
    arb_us: jax.Array,       # [K]
    e_op_uj: jax.Array,      # [K, 2, P] phase energies (zeros: end-time only)
    cls: jax.Array,          # [L] one fixed-size chunk of the trace
    channel: jax.Array,      # [L]
    way: jax.Array,          # [L]
    parity: jax.Array,       # [L]
    arrival_us: jax.Array,   # [L]
    extra_us: jax.Array,     # [L]
    valid: jax.Array,        # [L] bool; False = padding (state no-op)
    bus_free: jax.Array,     # [C]        carried occupancy state
    chip_free: jax.Array,    # [C, MAX_WAYS]
    ctrl_free: jax.Array,    # []
    round_start: jax.Array,  # [C]
    energy_acc: jax.Array,   # [P] carried phase-energy accumulator (uJ)
    n_channels: int,
    batched: bool,
) -> tuple[tuple, jax.Array, jax.Array, jax.Array]:
    """One chunk of the streaming engine (DESIGN.md §2.7): fold ``L``
    masked ops starting *from a caller-supplied occupancy state* and
    return ``((bus, chip, ctrl, round_start), energy_acc, end_us,
    comp[L])``.  This is the segment-product recurrence of §2.3
    specialised to its concrete carried state: because every chunk
    replays the exact per-op float sequence of ``_trace_step_fn`` (and
    masked padding is a bitwise state no-op), chaining chunks of *any*
    size reproduces the single-shot scan engine bit-for-bit — chunk-size
    invariance by construction, O(L) live memory regardless of trace
    length.  Energy adds ``where(valid, E[k, parity], 0)`` per step —
    adding +0.0 is exact, so the accumulator is chunk-invariant too."""
    upd = _trace_step_fn(cmd_us, pre_us, slot_us, post_lo_us, post_hi_us,
                         ctrl_us, arb_us, batched)

    def step(carry, op):
        state, acc = carry
        k, c, w, par, arr, ext, ok = op
        new = upd(state, (k, c, w, par, arr, ext))
        new = jax.tree.map(lambda n, o: jnp.where(ok, n, o), new, state)
        acc = acc + jnp.where(ok, e_op_uj[k, par % 2], jnp.float32(0.0))
        return (new, acc), new[1][c, w]           # chip_free[c, w]

    ops = _trace_ops(cls, channel, way, parity, arrival_us, extra_us) \
        + (valid.astype(bool),)
    init = ((bus_free, chip_free, ctrl_free, round_start), energy_acc)
    (state, acc), comp = jax.lax.scan(step, init, ops)
    end = jnp.maximum(jnp.max(state[0]), jnp.max(state[1]))
    return state, acc, end, comp


def trace_chunk_init(n_channels: int, n_phases: int):
    """Initial carry for :func:`trace_chunk_fold` — the zero occupancy
    state of ``_trace_scan_init`` plus a zero energy accumulator."""
    return (_trace_scan_init(n_channels),
            jnp.zeros((n_phases,), jnp.float32))


#: Dynamic dispatch rules evaluated inside the joint fold (sched-layer
#: names; the static policies lower offline in ``repro.core.sched``).
DISPATCH_RULES: tuple[str, ...] = ("least_loaded", "earliest_ready")


@functools.partial(jax.jit, static_argnames=("n_channels", "n_ways", "rule"))
def dispatch_trace(
    cmd_us: jax.Array,       # [K] op-class timing table
    pre_us: jax.Array,       # [K]
    slot_us: jax.Array,      # [K]
    post_lo_us: jax.Array,   # [K]
    post_hi_us: jax.Array,   # [K]
    ctrl_us: jax.Array,      # [K]
    arb_us: jax.Array,       # [K]
    cls: jax.Array,          # [T] int32 op-class per op (placement-free)
    arrival_us: jax.Array,   # [T] float32 request arrival per op
    n_channels: int,
    n_ways: int,
    rule: str = "least_loaded",
    extra_us: jax.Array | None = None,   # [T] reliability surcharge
    retired: jax.Array | None = None,    # [C, W] bool bad-block mask
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Joint dispatch + simulate fold (DESIGN.md §2.6): the carried
    occupancy row *drives* the channel/way assignment, one decision per
    op inside the same ``lax.scan`` that advances the timeline —
    the dynamic half of the scheduler layer (static policies lower
    offline to an ``OpTrace`` instead and reach every engine).

    Rules:

    * ``least_loaded``  — the op goes to the chip with the smallest
      busy horizon ``max(bus_free[c], chip_free[c, w])`` (global greedy
      on the op's earliest feasible start: an idle chip behind a
      saturated bus is *not* a good target; ties break to the lowest
      index);
    * ``earliest_ready`` — the op goes to the channel whose bus drains
      first, then to that channel's least-loaded way.

    Page parity is derived in-fold from a carried per-chip op counter
    (the dispatch decides which chip's MLC pair advances).  Returns
    ``(end_us, completion[T], channel[T], way[T], parity[T])`` — the
    chosen placement is a full ``OpTrace`` assignment, so energy /
    bandwidth accounting and the oracles replay it exactly.  Dispatch
    is FCFS in trace order under the ``eager`` issue policy (a strict
    ``batched`` round loop has no meaning when rounds are not fixed at
    build time).

    ``extra_us`` extends the op's chip occupancy / completion like the
    replay engines (DESIGN.md §2.8; never the channel bus or the serial
    controller); ``retired`` marks bad-block chips
    the dispatcher must never choose — their horizon is +inf under
    ``least_loaded`` and they are masked out of ``earliest_ready``'s
    way choice (each channel must keep >= 1 live way, which the
    ``FaultSampler`` retirement draw guarantees)."""
    if rule not in DISPATCH_RULES:
        raise ValueError(f"unknown dispatch rule {rule!r} "
                         f"(one of {', '.join(DISPATCH_RULES)})")
    least_loaded = rule == "least_loaded"
    if extra_us is None:
        extra_us = jnp.zeros_like(arrival_us, dtype=jnp.float32)
    if retired is None:
        retired = jnp.zeros((n_channels, n_ways), bool)
    retired = jnp.asarray(retired, bool)
    inf = jnp.asarray(jnp.inf, jnp.float32)

    def step(state, op):
        bus_free, chip_free, ctrl_free, counts = state
        k, arr, ext = op
        if least_loaded:
            horizon = jnp.where(retired, inf,
                                jnp.maximum(chip_free, bus_free[:, None]))
            flat = jnp.argmin(horizon.reshape(-1))
            c, w = flat // n_ways, flat % n_ways
        else:
            c = jnp.argmin(bus_free)
            w = jnp.argmin(jnp.where(retired[c], inf, chip_free[c]))
        par = counts[c, w] % 2
        ready = jnp.maximum(chip_free[c, w], arr) + cmd_us[k] + pre_us[k]
        start = (jnp.maximum(jnp.maximum(bus_free[c], ready), ctrl_free)
                 + arb_us[k])
        new_bus = start + slot_us[k]
        post = jnp.where(par % 2 == 0, post_lo_us[k], post_hi_us[k])
        comp = new_bus + post + ext
        state = (bus_free.at[c].set(new_bus),
                 chip_free.at[c, w].set(comp),
                 start + ctrl_us[k],
                 counts.at[c, w].add(1))
        return state, (comp, c.astype(jnp.int32), w.astype(jnp.int32),
                       par.astype(jnp.int32))

    init = (jnp.zeros((n_channels,), jnp.float32),
            jnp.zeros((n_channels, n_ways), jnp.float32),
            jnp.asarray(0.0, jnp.float32),
            jnp.zeros((n_channels, n_ways), jnp.int32))
    (bus_free, chip_free, _, _), (comp, chan, way, par) = jax.lax.scan(
        step, init, (cls.astype(jnp.int32), arrival_us.astype(jnp.float32),
                     extra_us.astype(jnp.float32)))
    end = jnp.maximum(jnp.max(bus_free), jnp.max(chip_free))
    return end, comp, chan, way, par


# ---------------------------------------------------------------------------
# Log-depth engines (DESIGN.md §2.3)
# ---------------------------------------------------------------------------


def _trace_end_time_prefix_impl(
        cmd_us, pre_us, slot_us, post_lo_us, post_hi_us, ctrl_us, arb_us,
        cls, channel, way, parity, arrival, extra, n_channels, n_ways,
        batched, segment_len, combine, valid=None):
    from repro.core import maxplus_form as mf  # deferred: mf imports us

    prods = mf.structured_segment_products(
        cmd_us, pre_us, slot_us, post_lo_us, post_hi_us, ctrl_us, arb_us,
        cls, channel, way, parity, arrival, extra,
        channels=n_channels, ways=n_ways, batched=batched,
        segment_len=segment_len if segment_len is not None else 1,
        valid=valid)
    layout = mf.StateLayout(n_channels, n_ways)
    s0 = jnp.zeros((layout.n_state,), jnp.float32)
    if combine == "assoc":        # log-depth dense combine (TPU-shaped)
        pref = jax.lax.associative_scan(
            lambda x, y: mf.maxplus_matmul(y, x), prods, axis=0)
        final = mf.maxplus_matvec(pref[-1], s0)
    elif combine == "chain":      # O(S) matvec chain: no dense matmuls,
        final, _ = jax.lax.scan(  # the CPU-fast combine
            lambda s, p: (mf.maxplus_matvec(p, s), None), s0, prods)
    else:
        raise ValueError(f"unknown combine {combine!r} "
                         "(one of 'chain', 'assoc')")
    return jnp.max(final[: layout.n_completion_rows])


@functools.partial(jax.jit, static_argnames=("n_channels", "n_ways",
                                             "batched", "segment_len",
                                             "combine"))
def trace_end_time_prefix(
    cmd_us: jax.Array,       # [K] op-class timing table
    pre_us: jax.Array,       # [K]
    slot_us: jax.Array,      # [K]
    post_lo_us: jax.Array,   # [K]
    post_hi_us: jax.Array,   # [K]
    ctrl_us: jax.Array,      # [K]
    arb_us: jax.Array,       # [K]
    cls: jax.Array,          # [T]
    channel: jax.Array,      # [T]
    way: jax.Array,          # [T]
    parity: jax.Array,       # [T]
    arrival_us: jax.Array,   # [T]
    extra_us: jax.Array,     # [T]
    n_channels: int,
    n_ways: int,
    batched: bool,
    segment_len: int | None = 64,
    combine: str = "chain",
    valid: jax.Array | None = None,   # [T] bool: False lanes skip exactly
) -> jax.Array:
    """Same recurrence as ``trace_end_time``, evaluated in O(L + S)
    depth (S = ceil(T/L)): the trace's S segment products are computed
    concurrently by the structured row fold of
    ``repro.core.maxplus_form.structured_segment_products`` (the scan
    recurrence on N-row-valued resource times — O(T·N) work, depth L),
    then combined across segments.  ``combine="chain"`` folds the S
    products into the initial state with O(S) cheap (max,+) matvecs
    (fastest on CPU); ``combine="assoc"`` combines them with a
    log-depth ``associative_scan`` of dense matmuls — O(L + log S)
    total depth, the shape meant for the TPU's MXU (its TPU speed is not
    measured yet).  Compiles end to end from the raw table/trace arrays
    with no Python pass over the trace.

    ``n_ways`` bounds the way indices in the trace and sets the state
    layout (smaller than the scan engine's fixed MAX_WAYS block, so the
    combine matrices stay compact).  ``segment_len=None`` folds each op
    as its own segment — with ``combine="assoc"`` the pure O(log T)-
    depth dense form.

    ``valid`` (optional [T] bool) masks lanes out of the product
    exactly — the masked-fold identity for sparsely padded traces
    (the fused FTL sweep's emission rows, DESIGN.md §2.11)."""
    return _trace_end_time_prefix_impl(
        cmd_us, pre_us, slot_us, post_lo_us, post_hi_us, ctrl_us, arb_us,
        cls, channel, way, parity, arrival_us, extra_us, n_channels,
        n_ways, batched, segment_len, combine, valid)


@functools.partial(jax.jit, static_argnames=("n_channels", "n_ways",
                                             "batched", "segment_len",
                                             "combine"))
def trace_end_time_prefix_energy(
    cmd_us: jax.Array,       # [K] op-class timing table
    pre_us: jax.Array,       # [K]
    slot_us: jax.Array,      # [K]
    post_lo_us: jax.Array,   # [K]
    post_hi_us: jax.Array,   # [K]
    ctrl_us: jax.Array,      # [K]
    arb_us: jax.Array,       # [K]
    e_op_uj: jax.Array,      # [K, 2, P] per-op phase energies
    cls: jax.Array,          # [T]
    channel: jax.Array,      # [T]
    way: jax.Array,          # [T]
    parity: jax.Array,       # [T]
    arrival_us: jax.Array,   # [T]
    extra_us: jax.Array,     # [T]
    n_channels: int,
    n_ways: int,
    batched: bool,
    segment_len: int | None = 64,
    combine: str = "chain",
) -> tuple[jax.Array, jax.Array]:
    """(end_us, [P] phase-energy sums in uJ) via the segmented prefix
    engine: energy is (+, +)-linear in the ops, so it rides the same
    segment chunking as ``structured_segment_products`` as a plain
    per-segment sum combined across segments (DESIGN.md §2.4)."""
    from repro.core import maxplus_form as mf  # deferred: mf imports us

    end = _trace_end_time_prefix_impl(
        cmd_us, pre_us, slot_us, post_lo_us, post_hi_us, ctrl_us, arb_us,
        cls, channel, way, parity, arrival_us, extra_us, n_channels,
        n_ways, batched, segment_len, combine)
    seg = mf.structured_segment_energy(
        e_op_uj, cls, parity,
        segment_len=segment_len if segment_len is not None else 1)
    return end, jnp.sum(seg, axis=0)


@functools.partial(jax.jit, static_argnames=("n_channels", "n_ways",
                                             "batched", "segment_len",
                                             "combine"))
def trace_end_time_prefix_batch(
    cmd_us: jax.Array,       # [B, K] stacked op-class timing tables
    pre_us: jax.Array,       # [B, K]
    slot_us: jax.Array,      # [B, K]
    post_lo_us: jax.Array,   # [B, K]
    post_hi_us: jax.Array,   # [B, K]
    ctrl_us: jax.Array,      # [B, K]
    arb_us: jax.Array,       # [B, K]
    cls: jax.Array,          # [T] one trace shared by the batch
    channel: jax.Array,      # [T]
    way: jax.Array,          # [T]
    parity: jax.Array,       # [T]
    arrival_us: jax.Array,   # [T]
    extra_us: jax.Array,     # [T]
    n_channels: int,
    n_ways: int,
    batched: bool,
    segment_len: int | None = 64,
    combine: str = "chain",
) -> jax.Array:
    """[B] completion times: one trace under a batch of design-point
    timing tables.  The structured segment fold vectorises over B×S
    lanes in one pass — the sweep-scaling form of the prefix engine
    (trace-only mask/pattern precomputation is shared across the
    batch)."""
    return jax.vmap(
        lambda *t: _trace_end_time_prefix_impl(
            *t, cls, channel, way, parity, arrival_us, extra_us,
            n_channels, n_ways, batched, segment_len, combine)
    )(cmd_us, pre_us, slot_us, post_lo_us, post_hi_us, ctrl_us, arb_us)


@functools.partial(jax.jit, static_argnames=("n_pages", "batched"))
def _squaring_end_time(
    cmd_us: jax.Array,       # scalars (or [B] under vmap) — one op class
    pre_us: jax.Array,
    slot_us: jax.Array,
    post_lo_us: jax.Array,
    post_hi_us: jax.Array,
    ctrl_us: jax.Array,
    ways: jax.Array,
    n_pages: int,
    batched: bool,
) -> jax.Array:
    """Homogeneous single-channel completion time via periodic matrix
    squaring: fold one 2·MAX_WAYS-op period block with the structured
    row fold, then square to ``n_pages`` — O(log n_pages) dense (max,+)
    matmuls plus one structured remainder fold (DESIGN.md §2.3).
    Requires ways | MAX_WAYS so the block is a whole number of true
    periods (the paper's power-of-two sweep grid)."""
    from repro.core import maxplus_form as mf  # deferred: mf imports us

    period = 2 * MAX_WAYS
    table = tuple(jnp.reshape(x, (1,)) for x in (
        cmd_us, pre_us, slot_us, post_lo_us, post_hi_us, ctrl_us,
        jnp.float32(0.0)))  # weak 0.0 would x64-promote the gathered table

    def block_product(n_ops: int) -> jax.Array:
        i = jnp.arange(n_ops)
        return mf.structured_segment_products(
            *table, jnp.zeros((n_ops,), jnp.int32),
            jnp.zeros((n_ops,), jnp.int32), (i % ways).astype(jnp.int32),
            ((i // ways) % 2).astype(jnp.int32),
            channels=1, ways=MAX_WAYS, batched=batched,
            segment_len=n_ops)[0]

    q, r = divmod(int(n_pages), period)
    if q:
        total = mf.maxplus_matrix_power(block_product(period), q)
        if r:
            total = mf.maxplus_matmul(block_product(r), total)
    else:
        total = block_product(r)
    s0 = jnp.zeros((mf.N_STATE,), jnp.float32)
    final = mf.maxplus_matvec(total, s0)
    return jnp.max(final[: mf.DEFAULT_LAYOUT.n_completion_rows])


@functools.partial(jax.jit, static_argnames=("n_channels", "batched"))
def trace_end_time_batch(
    cmd_us: jax.Array,       # [B, K] stacked tables (see trace_end_time)
    pre_us: jax.Array,
    slot_us: jax.Array,
    post_lo_us: jax.Array,
    post_hi_us: jax.Array,
    ctrl_us: jax.Array,
    arb_us: jax.Array,
    cls: jax.Array,          # [T] one trace shared by the batch
    channel: jax.Array,
    way: jax.Array,
    parity: jax.Array,
    arrival_us: jax.Array,
    extra_us: jax.Array,
    n_channels: int,
    batched: bool,
) -> jax.Array:
    """[B] completion times — the scan engine vmapped over tables."""
    return jax.vmap(
        lambda *t: trace_end_time(
            *t, cls, channel, way, parity, arrival_us, extra_us,
            n_channels=n_channels, batched=batched)
    )(cmd_us, pre_us, slot_us, post_lo_us, post_hi_us, ctrl_us, arb_us)


def _validate_squaring_ways(ways) -> None:
    """engine="squaring" folds a 2·MAX_WAYS-op period block, which only
    tiles the stream when ways | MAX_WAYS (the paper's power-of-two
    grid) — reject anything else loudly rather than silently misalign.
    Traced values can't be inspected; the precondition then stands as
    documented."""
    try:
        arr = np.asarray(ways)
    except Exception:                  # jax tracer: defer to the docs
        return
    if np.any(arr < 1) or np.any(MAX_WAYS % np.maximum(arr, 1) != 0):
        raise ValueError(
            f"engine='squaring' requires ways dividing {MAX_WAYS}, got "
            f"{arr.tolist()}")


def _steady_pattern(n_pages, ways):
    """way/parity index pattern of a single-channel round-robin stream."""
    i = jnp.arange(n_pages)
    return jnp.mod(i, ways).astype(jnp.int32), ((i // ways) % 2).astype(jnp.int32)


def channel_bandwidth_mb_s(
    op: PageOpParams,
    ways: int | jax.Array,
    policy: Policy = "eager",
    n_pages: int = 512,
    engine: Engine = "scan",
) -> jax.Array:
    """Deprecated shim: use
    ``repro.api.steady_channel_bandwidth_mb_s`` (same arguments, engine
    dispatch through the registry).  Numerically identical."""
    from repro.core import api
    warnings.warn(
        "repro.core.sim.channel_bandwidth_mb_s is deprecated; use "
        "repro.api.steady_channel_bandwidth_mb_s",
        DeprecationWarning, stacklevel=2)
    return api.steady_channel_bandwidth_mb_s(
        op, ways, policy=policy, n_pages=n_pages, engine=engine)


def ssd_bandwidth_mb_s(cfg: SSDConfig, mode: Mode, n_pages: int = 512) -> float:
    """Deprecated shim: use ``repro.api.steady_bandwidth_mb_s`` (same
    joint multi-channel simulation through a cached ``Simulator``
    session).  Numerically identical."""
    from repro.core import api
    warnings.warn(
        "repro.core.sim.ssd_bandwidth_mb_s is deprecated; use "
        "repro.api.steady_bandwidth_mb_s",
        DeprecationWarning, stacklevel=2)
    return api.steady_bandwidth_mb_s(cfg, mode, n_pages=n_pages)


# ---------------------------------------------------------------------------
# Closed-form steady-state model (tests & napkin math)
# ---------------------------------------------------------------------------


def steady_state_mb_s(op: PageOpParams, ways: int) -> float:
    """Ideal round-robin steady state: min(bus-bound, chip-bound) rate."""
    bus_rate = op.data_bytes / op.slot_us
    cycle = op.cmd_us + op.pre_us + op.slot_us + op.post_mean_us()
    chip_rate = ways * op.data_bytes / cycle
    return min(bus_rate, chip_rate)


def saturation_ways(op: PageOpParams) -> int:
    """Smallest W with W*slot >= full chip cycle (paper's saturation point)."""
    cycle = op.cmd_us + op.pre_us + op.slot_us + op.post_mean_us()
    return max(1, math.ceil(cycle / op.slot_us))


# ---------------------------------------------------------------------------
# Batched design-space sweep (vmap over design points)
# ---------------------------------------------------------------------------


def sweep_bandwidth_mb_s(
    cmd_us: jax.Array,
    pre_us: jax.Array,
    slot_us: jax.Array,
    post_lo_us: jax.Array,
    post_hi_us: jax.Array,
    ctrl_us: jax.Array,
    data_bytes: jax.Array,
    ways: jax.Array,
    n_pages: int = 512,
    batched: bool = False,
    engine: Engine = "scan",
) -> jax.Array:
    """Deprecated shim: use ``repro.api.sweep_steady_bandwidth_mb_s``
    (same arguments, engine dispatch through the registry).
    Numerically identical."""
    from repro.core import api
    warnings.warn(
        "repro.core.sim.sweep_bandwidth_mb_s is deprecated; use "
        "repro.api.sweep_steady_bandwidth_mb_s",
        DeprecationWarning, stacklevel=2)
    return api.sweep_steady_bandwidth_mb_s(
        cmd_us, pre_us, slot_us, post_lo_us, post_hi_us, ctrl_us,
        data_bytes, ways, n_pages=n_pages, batched=batched, engine=engine)


@functools.partial(jax.jit, static_argnames=("n_pages", "batched"))
def _sweep_scan_jit(
    cmd_us, pre_us, slot_us, post_lo_us, post_hi_us, ctrl_us,
    data_bytes, ways, n_pages: int, batched: bool,
) -> jax.Array:
    """Scan-engine half of the homogeneous design-point sweep: charges
    the shared-controller occupancy ``ctrl_us`` exactly like the
    per-point channel path (the two are regression-pinned equal)."""
    zeros_i = jnp.zeros((n_pages,), jnp.int32)
    zeros_f = jnp.zeros((n_pages,), jnp.float32)
    zero_k = jnp.zeros((1,), jnp.float32)

    def one(cmd, pre, slot, lo, hi, ctrl, nbytes, w):
        way, parity = _steady_pattern(n_pages, w)
        end = trace_end_time(
            cmd[None], pre[None], slot[None], lo[None], hi[None],
            ctrl[None], zero_k, zeros_i, zeros_i, way, parity, zeros_f,
            zeros_f, n_channels=1, batched=batched)
        return (n_pages * nbytes) / end

    return jax.vmap(one)(cmd_us, pre_us, slot_us, post_lo_us, post_hi_us,
                         ctrl_us, data_bytes, ways)


@functools.partial(jax.jit, static_argnames=("n_pages", "batched"))
def _sweep_squaring_jit(
    cmd_us, pre_us, slot_us, post_lo_us, post_hi_us, ctrl_us,
    data_bytes, ways, n_pages: int, batched: bool,
) -> jax.Array:
    """Squaring-engine half of the sweep: each point in O(log n_pages)
    (max,+) matmuls (every entry of ``ways`` must divide MAX_WAYS —
    validated by the caller, since tracers cannot be inspected here)."""

    def one_sq(cmd, pre, slot, lo, hi, ctrl, nbytes, w):
        end = _squaring_end_time(cmd, pre, slot, lo, hi, ctrl, w,
                                 n_pages=n_pages, batched=batched)
        return (n_pages * nbytes) / end

    return jax.vmap(one_sq)(cmd_us, pre_us, slot_us, post_lo_us,
                            post_hi_us, ctrl_us, data_bytes, ways)
