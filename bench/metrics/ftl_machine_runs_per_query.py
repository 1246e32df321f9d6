"""Launches of the FTL translation fold per query, preconditioning
included: the ``machine_runs`` counters of the ``ssd.translate`` spans."""

import program_spans


def read(r):
    return program_spans.METRICS["ftl_machine_runs_per_query"](r.program_spans, r.queries)
