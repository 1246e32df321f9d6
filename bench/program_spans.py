"""The simulator's own spans, read from a profiler trace.

The program opens a ``jax.profiler.TraceAnnotation`` named ``ssd.<stage>``
at each layer boundary of a query (``repro/core/spans.py`` lists them and
their stats).  They sit on the host plane beside the harness's
``bench.*`` spans, on the clock of the device programs;
``trace_reduce.read_xplane`` reads them with their stats, this module
clips them to the traced window (``Reduced.program_spans``) and reduces
them to the per-stage numbers below, each read by the per-layer metric
of its name (``bench/metrics/<number>.py``).  A trace of a program
without such spans gives no spans, and every number here is then
``None``.

A span is ``(stage, start_ns, end_ns, stats)``, the stage without the
``ssd.`` prefix.  Spans nest by containment on the calling thread.

| number | reads |
| --- | --- |
| ``stage_ms_per_query`` | time in ``ssd.stage`` per query |
| ``fold_wall_ms_per_query`` | time in ``ssd.fold`` per query, on the host clock |
| ``lower_ms_per_query`` | time in ``ssd.lower`` per query |
| ``extract_ms_per_query`` | time in ``ssd.extract`` per query |
| ``precondition_ms_per_query`` | time in ``ssd.precondition`` per query |
| ``translate_ms_per_query`` | time in ``ssd.translate`` outside ``ssd.precondition``, per query |
| ``fold_pad_pct`` | 100 (1 - sum of ``ops`` / sum of ``steps``) over ``ssd.fold`` |
| ``ftl_machine_runs_per_query`` | sum of ``machine_runs`` of ``ssd.translate`` per query |
"""

from __future__ import annotations

import re

from trace_reduce import PROGRAM_PREFIX, covered

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def from_events(host_events, window) -> list[tuple]:
    """The program spans of ``host_events`` = [(name, start_ns, dur_ns,
    stats)], names as the trace has them, clipped to ``window``; spans
    wholly outside it are dropped."""
    lo, hi = window
    out = []
    for n, s, d, stats in host_events:
        if not n.startswith(PROGRAM_PREFIX) or s + d <= lo or s >= hi:
            continue
        out.append((n[len(PROGRAM_PREFIX):], max(s, lo), min(s + d, hi),
                    dict(stats)))
    return sorted(out, key=lambda sp: (sp[1], -sp[2]))


def device_lines(path: str) -> dict[str, dict]:
    """The events per line of each device plane of an ``.xplane.pb``
    file (where a line holds fewer programs than were run, the profiler
    dropped events)."""
    from jax.profiler import ProfileData

    return {plane.name: {line.name: sum(1 for _ in line.events)
                         for line in plane.lines}
            for plane in ProfileData.from_file(path).planes
            if _DEVICE.match(plane.name)}


def stage_ms(spans, stage: str) -> float:
    """Milliseconds in which some span of ``stage`` was open."""
    return covered([(a, b) for n, a, b, _ in spans if n == stage],
                   float("-inf"), float("inf")) * 1e-6


def _per_query(spans, stage: str, queries: int):
    if not queries or not any(n == stage for n, *_ in spans):
        return None
    return stage_ms(spans, stage) / queries


def stage_ms_per_query(spans, queries):
    return _per_query(spans, "stage", queries)


def fold_wall_ms_per_query(spans, queries):
    return _per_query(spans, "fold", queries)


def lower_ms_per_query(spans, queries):
    return _per_query(spans, "lower", queries)


def extract_ms_per_query(spans, queries):
    return _per_query(spans, "extract", queries)


def precondition_ms_per_query(spans, queries):
    return _per_query(spans, "precondition", queries)


def translate_ms_per_query(spans, queries):
    """The translation's own time: ``ssd.translate`` less the
    ``ssd.precondition`` inside it."""
    whole = _per_query(spans, "translate", queries)
    if whole is None:
        return None
    trans = [(a, b) for n, a, b, _ in spans if n == "translate"]
    pre = sum(covered(trans, a, b)
              for n, a, b, _ in spans if n == "precondition")
    return whole - pre * 1e-6 / queries


def fold_pad_pct(spans, queries=None):
    """Share of the folds' scan steps that ran on padding."""
    folds = [st for n, _, _, st in spans if n == "fold"]
    steps = sum(int(st.get("steps", 0)) for st in folds)
    if not steps:
        return None
    ops = sum(int(st.get("ops", 0)) for st in folds)
    return 100.0 * (1.0 - ops / steps)


def ftl_machine_runs_per_query(spans, queries):
    """Launches of the translation fold per query, preconditioning
    included."""
    runs = [st["machine_runs"] for n, _, _, st in spans
            if n == "translate" and "machine_runs" in st]
    if not queries or not runs:
        return None
    return sum(int(r) for r in runs) / queries


#: Every per-stage number, by name.
METRICS = {f.__name__: f for f in (
    stage_ms_per_query, fold_wall_ms_per_query, lower_ms_per_query,
    extract_ms_per_query, precondition_ms_per_query,
    translate_ms_per_query, fold_pad_pct, ftl_machine_runs_per_query)}


def query_coverage(spans, queries) -> list[float]:
    """For each query span (start_ns, end_ns), the share of it that some
    program span covers."""
    ivs = [(a, b) for _, a, b, _ in spans]
    return [covered(ivs, a, b) / (b - a) if b > a else 1.0
            for a, b in queries]


def fold_device_cover(spans, device_intervals) -> list[dict]:
    """For each ``ssd.fold`` span, its stats, its length and the share of
    it in which a device ran a program."""
    out = []
    for n, a, b, st in spans:
        if n == "fold":
            out.append({**st, "ms": (b - a) * 1e-6,
                        "device_share": (covered(device_intervals, a, b)
                                         / (b - a) if b > a else 0.0)})
    return out
