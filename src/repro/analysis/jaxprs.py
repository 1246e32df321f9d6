"""Layer 2: jaxpr-level invariant checks (DESIGN.md §2.9).

Every registered engine exposes ``canonical_folds`` — a hook returning
``{label: (fn, args)}`` closures over one canonical small request
(``repro.core.api._canonical_trace``: 48 mixed ops on a 2x4 MLC
geometry, staggered arrivals, sparse extra stalls).  This module traces
each closure with :func:`jax.make_jaxpr` and statically asserts the
contracts the engines' bit-for-bit claim rests on:

``jaxpr-hook``
    every registered engine must implement the hook (``None`` opts a
    host-Python engine out of tracing — the AST layer still lints it);
``jaxpr-rng``
    zero RNG primitives anywhere in a fold — randomness is sampled
    outside the folds from seeded streams (PR 7 determinism contract);
``jaxpr-dtype``
    no f64 value anywhere, floating outputs exactly f32.  Each fold is
    traced twice: once under the default config and once under
    ``jax.enable_x64(True)`` — f32 discipline must come from
    explicit dtypes, not from the global f64 demotion silently papering
    over weak-type promotion;
``pad-identity``
    padding a masked fold to a larger power-of-two bucket is a (max,+)
    identity: the padded end time equals the unpadded scan bit-for-bit
    (checked by running the jitted folds, not by tracing);
``jaxpr-budget``
    per-fold primitive counts vs the committed baseline
    (:mod:`repro.analysis.baseline`).

The walk recurses into every sub-jaxpr (scan/while/pjit/pallas_call
bodies) by duck-typing eqn params: anything with ``.eqns`` is a Jaxpr,
anything with ``.jaxpr`` wraps one, tuples/lists are searched.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Iterable

from repro.analysis.findings import Finding

#: substrings identifying RNG primitives (threefry2x32, random_bits,
#: random_seed/wrap/fold_in/gamma, rng_bit_generator, ...)
RNG_PRIMITIVE_MARKERS: tuple[str, ...] = ("random", "threefry", "rng")


@dataclasses.dataclass(frozen=True)
class EngineFold:
    """One traced canonical fold of one engine."""

    engine: str
    label: str                 # hook key, e.g. "end_time"
    n_primitives: int          # total eqn count, sub-jaxprs included
    primitive_counts: dict     # name -> count (diagnostics/JSON)
    host: bool = False         # True for opted-out host-Python engines

    @property
    def key(self) -> str:
        return f"{self.engine}/{self.label}"


def _iter_subjaxprs(param) -> Iterable:
    """Yield every Jaxpr reachable from one eqn param value."""
    if hasattr(param, "eqns"):          # core.Jaxpr
        yield param
    elif hasattr(param, "jaxpr"):       # ClosedJaxpr and friends
        yield from _iter_subjaxprs(param.jaxpr)
    elif isinstance(param, (tuple, list)):
        for item in param:
            yield from _iter_subjaxprs(item)


def walk_eqns(jaxpr, visit: Callable) -> None:
    """Call ``visit(eqn)`` for every equation, recursing into the
    scan/while/pjit/pallas_call sub-jaxprs carried in eqn params."""
    for eqn in jaxpr.eqns:
        visit(eqn)
        for param in eqn.params.values():
            for sub in _iter_subjaxprs(param):
                walk_eqns(sub, visit)


def _is_rng_primitive(name: str) -> bool:
    return any(m in name for m in RNG_PRIMITIVE_MARKERS)


def _eqn_dtypes(eqn) -> Iterable[str]:
    for var in list(eqn.invars) + list(eqn.outvars):
        aval = getattr(var, "aval", None)
        dtype = getattr(aval, "dtype", None)
        if dtype is not None:
            yield str(dtype)


def canonical_simulator():
    """The session every fold is traced under: the canonical 2x4 MLC
    geometry (MLC exercises the lower/upper-page parity asymmetry)."""
    from repro.core.api import Simulator
    from repro.core.nand import CellType
    from repro.core.sim import SSDConfig

    return Simulator(SSDConfig(cell=CellType.MLC, channels=2, ways=4))


def _registered_engines() -> dict:
    from repro.core import api

    return {name: api.get_engine(name) for name in api.registered_engines()}


def _check_one(engine: str, label: str, fn, args,
               findings: list[Finding]) -> EngineFold:
    import jax

    key = f"{engine}/{label}"
    closed = jax.make_jaxpr(fn)(*args)

    counts: dict[str, int] = {}
    f64_hits: list[str] = []

    def visit(eqn):
        name = eqn.primitive.name
        counts[name] = counts.get(name, 0) + 1
        if _is_rng_primitive(name):
            findings.append(Finding(
                rule="jaxpr-rng", path=key, line=0,
                message=f"RNG primitive {name!r} inside the fold "
                        "(randomness must be sampled outside, from "
                        "seeded streams)"))
        if any(d == "float64" for d in _eqn_dtypes(eqn)):
            f64_hits.append(name)

    walk_eqns(closed.jaxpr, visit)

    for aval in closed.out_avals:
        dtype = str(getattr(aval, "dtype", ""))
        if dtype.startswith("float") and dtype != "float32":
            findings.append(Finding(
                rule="jaxpr-dtype", path=key, line=0,
                message=f"floating output is {dtype}, expected float32"))
    if f64_hits:
        findings.append(Finding(
            rule="jaxpr-dtype", path=key, line=0,
            message="float64 values in fold (via "
                    f"{', '.join(sorted(set(f64_hits)))})"))

    # Retrace with x64 enabled: a weak python-float constant that the
    # default config silently demotes to f32 promotes to f64 here.
    with jax.enable_x64(True):
        closed64 = jax.make_jaxpr(fn)(*args)
    f64_hits_x64: list[str] = []

    def visit64(eqn):
        if any(d == "float64" for d in _eqn_dtypes(eqn)):
            f64_hits_x64.append(eqn.primitive.name)

    walk_eqns(closed64.jaxpr, visit64)
    for aval in closed64.out_avals:
        if str(getattr(aval, "dtype", "")) == "float64":
            f64_hits_x64.append("<output>")
    if f64_hits_x64:
        findings.append(Finding(
            rule="jaxpr-dtype", path=key, line=0,
            message="weak-type f64 promotion under enable_x64 (via "
                    f"{', '.join(sorted(set(f64_hits_x64)))}); pin the "
                    "constant/array to an explicit float32 dtype"))

    return EngineFold(engine=engine, label=label,
                      n_primitives=sum(counts.values()),
                      primitive_counts=counts)


def collect_engine_folds(
        engines: dict | None = None,
        sim=None) -> tuple[list[EngineFold], list[Finding]]:
    """Trace every registered engine's canonical folds.

    ``engines``/``sim`` exist for test injection (a fake engine dict, a
    different geometry); the CLI always uses the live registry.
    """
    if engines is None:
        engines = _registered_engines()
    if sim is None:
        sim = canonical_simulator()

    folds: list[EngineFold] = []
    findings: list[Finding] = []
    for name in sorted(engines):
        engine = engines[name]
        try:
            hooks = engine.canonical_folds(sim)
        except NotImplementedError as exc:
            findings.append(Finding(
                rule="jaxpr-hook", path=f"engine:{name}", line=0,
                message=str(exc)))
            continue
        if hooks is None:
            folds.append(EngineFold(engine=name, label="host",
                                    n_primitives=0, primitive_counts={},
                                    host=True))
            continue
        for label, (fn, args) in sorted(hooks.items()):
            try:
                folds.append(_check_one(name, label, fn, args, findings))
            except Exception as exc:  # tracing itself blew up
                findings.append(Finding(
                    rule="jaxpr-hook", path=f"{name}/{label}", line=0,
                    message=f"canonical fold failed to trace: "
                            f"{type(exc).__name__}: {exc}"))
    return folds, findings


def check_padding_identity(sim=None) -> list[Finding]:
    """Run (not trace) the masked folds: padding the canonical trace to
    a larger power-of-two bucket must leave the end time bit-identical
    to the unpadded scan — the pad op is the (max,+) identity."""
    import jax.numpy as jnp

    from repro.core import api, sim as _sim

    if sim is None:
        sim = canonical_simulator()
    trace = api._canonical_trace()
    findings: list[Finding] = []

    base = float(_sim.trace_end_time(
        *sim._targs, *api._trace_args(trace),
        n_channels=trace.channels, batched=False))

    for bucket in (64, 128):
        padded = float(_sim.trace_end_time_masked(
            *sim._targs, *api._padded_trace_args(trace, bucket),
            n_channels=trace.channels, batched=False))
        if padded != base:
            findings.append(Finding(
                rule="pad-identity", path=f"scan/masked[{bucket}]", line=0,
                message=f"padding to bucket {bucket} changed the end "
                        f"time: {padded!r} != {base!r} (pad row is not "
                        "a (max,+) identity)"))

    # Streaming: chunked fold over the same padded operands must agree.
    e_tab = jnp.zeros((sim.table.n_classes, 2, 1), jnp.float32)
    for bucket in (64, 128):
        carry = _sim.trace_chunk_init(trace.channels, 1)
        _, _, end, _ = _sim.trace_chunk_fold(
            *sim._targs, e_tab, *api._padded_trace_args(trace, bucket),
            *api._carry_args(carry), n_channels=trace.channels,
            batched=False)
        streamed = float(end)
        if streamed != base:
            findings.append(Finding(
                rule="pad-identity", path=f"streaming/chunk[{bucket}]",
                line=0,
                message=f"chunked fold over bucket {bucket} changed the "
                        f"end time: {streamed!r} != {base!r}"))
    return findings
