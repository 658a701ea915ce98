"""Device time of the traced jobs' programs per run-iteration: the union
of the programs' device intervals in the traced window over the traced
jobs' run-iterations (device trace)."""


def read(run):
    s, n = run.summary, run.counters.get("run_iters")
    if s is None or not n or not s["program_ns"]:
        return None
    return s["program_ns"] / n
