"""Share of the traced window from each job's start to its first device op:
the host's case materialization, schedule sampling (``prepare``) and
stacking before anything runs on the chip (harness spans on the device
trace's clock)."""

from trace_reduce import first_start_in


def read(run):
    tr, s = run.trace, run.summary
    if tr is None or s is None:
        return None
    device = [e for evs in tr.ops.values() for e in evs]
    lead = 0
    for _, lo, hi in tr.spans_named("bench.step"):
        first = first_start_in(device, lo, hi)
        if first is not None:
            lead += first - lo
    return 100.0 * lead / s["window_ns"] if lead else None
