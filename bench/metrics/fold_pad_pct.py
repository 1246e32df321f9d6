"""Share of the engine folds' scan steps that ran on padding, in %:
100 (1 - ops / steps) over the ``ssd.fold`` spans' counters."""

import program_spans


def read(r):
    return program_spans.METRICS["fold_pad_pct"](r.program_spans, r.queries)
