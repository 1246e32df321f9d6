"""Host milliseconds per query in the simulator's ``ssd.lower`` spans:
static lowering of requests to page ops."""

import program_spans


def read(r):
    return program_spans.METRICS["lower_ms_per_query"](r.program_spans, r.queries)
