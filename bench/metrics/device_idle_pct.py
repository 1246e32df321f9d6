"""Share of the traced window in which no program ran on a device,
as a percentage, the mean over the chips used."""


def read(r):
    if not r.programs or r.window_s <= 0:
        return None
    busy = sum(r.busy_s(d) for d in r.programs) / len(r.programs)
    return 100.0 * (1.0 - busy / r.window_s)
