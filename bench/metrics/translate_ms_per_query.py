"""Milliseconds per query in the simulator's ``ssd.translate`` spans
outside ``ssd.precondition``: the FTL translation of the query's own
requests."""

import program_spans


def read(r):
    return program_spans.METRICS["translate_ms_per_query"](r.program_spans, r.queries)
