"""Host milliseconds per query: the harness's span around each query
(the program's lowering, extraction and dispatch) less the time in it
during which some chip ran a program."""

from trace_reduce import covered


def read(r):
    spans = r.query_spans()
    if not spans:
        return None
    busy = r.all_devices_intervals()
    host = [(b - a) - covered(busy, a, b) for a, b in spans]
    return sum(host) * 1e-6 / len(host)
