"""Share of the traced window in which no operation ran on the device:
1 - busy union / window, averaged over the cell's chips (device trace)."""


def read(run):
    s = run.summary
    if s is None:
        return None
    return 100.0 * (1.0 - s["busy_ns"] / s["window_ns"])
