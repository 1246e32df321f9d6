"""Host milliseconds per query in the simulator's ``ssd.extract`` spans:
latencies and the result read out of the fold's answer."""

import program_spans


def read(r):
    return program_spans.METRICS["extract_ms_per_query"](r.program_spans, r.queries)
