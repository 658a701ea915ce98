"""Device time of the traced training steps' programs per step: the
union of the programs' device intervals in the traced window over the
traced steps (device trace)."""


def read(run):
    s, n = run.summary, run.counters.get("steps")
    if s is None or not n or not s["program_ns"]:
        return None
    return s["program_ns"] / 1e6 / n
