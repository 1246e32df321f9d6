"""How unevenly the chips were busy, in %: the largest less the smallest
share of the traced window in which a program ran on a chip.  Nothing to
read on one chip."""


def read(r):
    if len(r.programs) < 2 or r.window_s <= 0:
        return None
    busy = [r.busy_s(d) / r.window_s for d in r.programs]
    return 100.0 * (max(busy) - min(busy))
