"""Milliseconds per query in the simulator's ``ssd.fold`` spans, on the
host clock: each jitted engine call from dispatch to its answer on the
host."""

import program_spans


def read(r):
    return program_spans.METRICS["fold_wall_ms_per_query"](r.program_spans, r.queries)
