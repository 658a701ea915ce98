"""Share of the roofline reached by the fused decode-combine + x-update
kernel: the least time its bytes and operations at the unpadded (J, n)
need on this chip (``work.coded_admm_update``), over the device time of
its events (device trace). Memory bandwidth binds at these shapes."""

import work
from trace_reduce import time_by_name

KERNEL = "coded_admm_update"


def read(run):
    tr, s, calls = run.trace, run.summary, run.counters.get(KERNEL)
    if tr is None or s is None or not calls:
        return None
    win = tr.window()
    ns = 0
    for evs in tr.ops.values():
        ns += sum(v for k, v in time_by_name(evs, *win).items() if KERNEL in k)
    if not ns:
        return None
    flops, nbytes = work.coded_admm_update(calls["J"], calls["n"])
    least_s = calls["calls"] * max(
        flops / run.peaks["flops_per_s"], nbytes / run.peaks["hbm_bytes_per_s"]
    )
    return 100.0 * least_s / (ns / 1e9 / len(tr.ops))
