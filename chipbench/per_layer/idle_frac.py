"""``idle_frac.*``: share of the traced window, in %, in which a chip
ran no operation, averaged over the chips used."""


def read(run):
    t = run.trace
    if t.window_s <= 0:
        return None
    idle = [1.0 - t.busy_ns(t.lo, t.hi, d) / (t.hi - t.lo)
            for d in t.devices]
    return 100.0 * sum(idle) / len(idle)
