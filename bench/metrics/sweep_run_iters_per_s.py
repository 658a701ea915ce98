"""Run-iterations of every job started in the window, over the window's
whole time to the end of the last of them (host clock)."""


def read(run):
    n = run.counters.get("run_iters")
    return n / run.window_s if n else None
