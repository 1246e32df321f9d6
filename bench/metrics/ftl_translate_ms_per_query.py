"""Device milliseconds per query of the FTL translation programs
(``core/ftl_scan``'s scan machine, preconditioning included)."""

#: Program names of the translation folds, as the trace shows them.
TRANSLATION = ("jit_fold",)


def read(r):
    if not r.queries:
        return None
    t = sum(r.busy_s(d, lambda n: n in TRANSLATION) for d in r.programs)
    return t * 1e3 / r.queries if t > 0 else None
