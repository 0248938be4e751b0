"""Device: share of the traced window in which no operation ran on the
chip, in percent (one minus the union of busy intervals over the
window, averaged over the chips used)."""


def read(r):
    if r.trace is None or r.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)
