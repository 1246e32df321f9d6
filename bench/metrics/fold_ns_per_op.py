"""Device nanoseconds per point-op of every program that is not FTL
translation: the engine folds (scan, prefix, sharded) and the small
programs around them, summed over chips."""

#: The FTL translation programs (``core/ftl_scan``'s scan machine).
TRANSLATION = ("jit_fold",)


def read(r):
    if not r.point_ops:
        return None
    t = sum(r.busy_s(d, lambda n: n not in TRANSLATION) for d in r.programs)
    return t * 1e9 / r.point_ops if t > 0 else None
