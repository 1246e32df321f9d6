"""Host milliseconds per query in the simulator's ``ssd.stage`` spans:
host arrays turned into device arguments at the engine calls."""

import program_spans


def read(r):
    return program_spans.METRICS["stage_ms_per_query"](r.program_spans, r.queries)
