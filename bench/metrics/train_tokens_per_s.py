"""Tokens of the rows that the committing agent trains on, over the
window: every step started in the window, to the end of the last
(host clock)."""


def read(run):
    n = run.counters.get("tokens")
    return n / run.window_s if n else None
