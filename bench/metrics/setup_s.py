"""Set-up: from the start of the process to the start of the window,
loading, device start, the warm-up and any compilation (host clock)."""


def read(run):
    return run.setup_s
