"""From a profiler trace to the numbers the per-layer metrics read.

The device planes (``/device:TPU:<n>``) carry one event per executed
program on their ``XLA Modules`` line, named ``<jit name>(<hash>)``.
The harness's own spans (``bench.query``, ``bench.generate``,
``bench.window``) sit on a host plane, and so do the program's own
(``ssd.<stage>``, with their stats as counters; ``program_spans``
reduces them).  Per-op events (the ``XLA Ops`` line, one per loop
iteration of a scan) are never read: programs are the unit every
metric here counts.

``Reduced`` is what a metric reader in ``bench/metrics/`` receives.
Intervals are (start_ns, end_ns) on the profiler's clock.
"""

from __future__ import annotations

import dataclasses
import re

SPAN_PREFIX = "bench."
PROGRAM_PREFIX = "ssd."
_HASH = re.compile(r"\(\d+\)$")


def program_name(event_name: str) -> str:
    """``jit_fold(4564964841494774586)`` -> ``jit_fold``."""
    return _HASH.sub("", event_name)


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, merged intervals."""
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def covered(intervals, lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` that the union of ``intervals`` covers."""
    return sum(max(0.0, min(b, hi) - max(a, lo))
               for a, b in union(intervals))


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of ``[lo, hi]`` that ``intervals`` leave uncovered."""
    out, at = [], lo
    for a, b in union(intervals):
        if b <= lo or a >= hi:
            continue
        if a > at:
            out.append((at, min(a, hi)))
        at = max(at, b)
    if at < hi:
        out.append((at, hi))
    return out


@dataclasses.dataclass
class Reduced:
    """One traced window, reduced.

    ``programs[d]`` lists (name, start_ns, end_ns) of device ``d``'s
    programs; ``spans`` lists (name, start_ns, end_ns) of the harness's
    spans, names without the ``bench.`` prefix; ``window`` is the traced
    window (start_ns, end_ns).  ``program_spans`` lists (stage,
    start_ns, end_ns, stats) of the program's ``ssd.<stage>`` spans,
    clipped to the window (``program_spans.from_events``), outer first;
    it is empty for a program without spans.  ``queries`` and
    ``point_ops`` count the whole queries the traced window ran and the
    work they asked for; ``compiles`` is the harness's count of
    compilations in the window.
    """

    programs: dict[int, list[tuple[str, float, float]]]
    spans: list[tuple[str, float, float]]
    window: tuple[float, float]
    program_spans: list[tuple[str, float, float, dict]] = \
        dataclasses.field(default_factory=list)
    queries: int = 0
    point_ops: int = 0
    compiles: int = 0

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def intervals(self, device: int, match=None) -> list[tuple[float, float]]:
        """Program intervals of one device, those whose name ``match``
        accepts when given."""
        return [(a, b) for n, a, b in self.programs.get(device, [])
                if match is None or match(n)]

    def busy_s(self, device: int, match=None) -> float:
        """Seconds of the window in which a matching program ran on
        ``device``."""
        return covered(self.intervals(device, match), *self.window) * 1e-9

    def all_devices_intervals(self) -> list[tuple[float, float]]:
        return [iv for d in self.programs for iv in self.intervals(d)]

    def query_spans(self) -> list[tuple[float, float]]:
        return [(a, b) for n, a, b in self.spans if n == "query"]


def from_events(device_events, host_events, window) -> Reduced:
    """Build from plain lists: ``device_events[d]`` = [(name, start_ns,
    dur_ns)], ``host_events`` = [(name, start_ns, dur_ns)] (only names
    starting with ``bench.`` are kept)."""
    programs = {d: [(program_name(n), s, s + du) for n, s, du in evs]
                for d, evs in device_events.items()}
    spans = [(n[len(SPAN_PREFIX):], s, s + du) for n, s, du in host_events
             if n.startswith(SPAN_PREFIX)]
    return Reduced(programs=programs, spans=spans, window=window)


_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def read_xplane(path: str):
    """(device_events, host_events, program_events) of an
    ``.xplane.pb`` file: the harness's host events as (name, start_ns,
    dur_ns), the program's as (name, start_ns, dur_ns, stats)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_events: dict[int, list] = {}
    host_events: list = []
    program_events: list = []
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            if m and line.name == "XLA Modules":
                device_events.setdefault(int(m.group(1)), []).extend(
                    (e.name, e.start_ns, e.duration_ns) for e in line.events)
            elif not m and plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host_events.append((e.name, e.start_ns,
                                            e.duration_ns))
                    elif e.name.startswith(PROGRAM_PREFIX):
                        program_events.append((e.name, e.start_ns,
                                               e.duration_ns, dict(e.stats)))
    return device_events, host_events, program_events


def innermost_idle(r: Reduced, top: int = 10) -> list[list]:
    """The traced window's idle time (no device ran a program), put down
    to the innermost span open then: a program span (``ssd.<stage>``)
    or a harness span (``query``, ``generate``), else ``harness`` (the
    loop between spans).  Seconds, largest first."""
    opened = ([(PROGRAM_PREFIX + n, a, b) for n, a, b, _ in r.program_spans]
              + [(n, a, b) for n, a, b in r.spans if n != "window"])
    idle: dict[str, float] = {}
    for lo, hi in gaps(r.all_devices_intervals(), *r.window):
        cuts = sorted({lo, hi} | {t for _, a, b in opened for t in (a, b)
                                  if lo < t < hi})
        for x, y in zip(cuts, cuts[1:]):
            inside = [(a, -b, n) for n, a, b in opened if a <= x and y <= b]
            name = max(inside)[2] if inside else "harness"
            idle[name] = idle.get(name, 0.0) + (y - x) * 1e-9
    return [[n, s] for n, s in sorted(idle.items(), key=lambda kv: -kv[1])
            [:top]]


def breakdown(r: Reduced, top: int = 10) -> dict:
    """The device programs that took most time (seconds summed over
    devices) and the window's idle time by the innermost open span
    (``innermost_idle``)."""
    per: dict[str, float] = {}
    for d in r.programs:
        for name, a, b in r.programs[d]:
            t = max(0.0, min(b, r.window[1]) - max(a, r.window[0]))
            per[name] = per.get(name, 0.0) + t * 1e-9
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": innermost_idle(r, top)}
