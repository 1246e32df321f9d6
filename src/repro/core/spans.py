"""Named host spans of the simulator's query path.

Each layer boundary of a query opens one span: a
``jax.profiler.TraceAnnotation`` named ``ssd.<stage>``.  A profiler
trace then puts a query's wall time down to a stage, on the host plane
and the clock of the device programs.  Spans nest by containment on
the calling thread:

``ssd.run`` / ``ssd.sweep``
    the root of one ``Simulator.run`` / ``Simulator.sweep`` call
    (stats ``call``: the session's call count, ``ops``, ``points``);
``ssd.translate``
    the compiled FTL translation, ``core/ftl_scan.translate_scan``
    (``host_ops``, ``out_ops``, ``machine_runs``);
``ssd.precondition``
    inside it, the aging of a fresh drive (``writes``,
    ``machine_runs``);
``ssd.lower``
    the scheduler's static lowering and the trace check (``ops``;
    ``ranked_ops``: the ops whose MLC page parity took the per-chip
    rank of ``core/trace._finalize``, 0 where every op advances a
    placement slot and the closed form gives it);
``ssd.stage``
    host arrays to device arguments at an engine call (``steps``);
``ssd.fold``
    one jitted engine call, from dispatch until its answer is on the
    host (``engine``, ``ops``: real ops, ``steps``: the steps the fold
    runs, the padded length but for the kernel, ``points``; the scan
    engine's ``end_time`` / ``completions`` also ``kernel``: 1 where
    the Pallas fold of ``repro.kernels.scanfold`` ran, 0 for the
    ``lax.scan``);
``ssd.extract``
    per-request latencies and the result's assembly.

``machine_runs`` counts launches of the translation fold: 1 when its
buffer estimate holds, one more on every doubling.  Counters ride as
span stats; those known only at a span's end go in through
``set_metadata``.  Spans sit in host code only, never inside a jitted
or scanned body, and end where the code already waits for the device,
so none adds a device sync.  With no profiler running a span costs
under a microsecond.
"""

from __future__ import annotations

import jax

#: Name prefix of every span the simulator opens.
PREFIX = "ssd."


def span(stage: str, **stats) -> jax.profiler.TraceAnnotation:
    """The span ``ssd.<stage>`` carrying ``stats`` (ints or strings)."""
    return jax.profiler.TraceAnnotation(PREFIX + stage, **stats)
