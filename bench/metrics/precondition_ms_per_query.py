"""Milliseconds per query in the simulator's ``ssd.precondition`` spans:
aging the fresh drive before the query's own writes."""

import program_spans


def read(r):
    return program_spans.METRICS["precondition_ms_per_query"](r.program_spans, r.queries)
