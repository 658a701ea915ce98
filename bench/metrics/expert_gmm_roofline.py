"""Share of the roofline reached by the grouped expert products: the
least time that their operations and bytes for the token-slots actually
routed to the held experts need on this chip, forward (with its
recomputation) and backward (``work_moe.expert_gmm_step``), over the
device time of the ops named ``expert_gmm*`` (device trace)."""

from trace_reduce import time_by_name

KERNEL = "expert_gmm"


def read(run):
    tr, s, work = run.trace, run.summary, run.counters.get(KERNEL)
    if tr is None or s is None or not work:
        return None
    win = tr.window()
    ns = 0
    for evs in tr.ops.values():
        ns += sum(v for k, v in time_by_name(evs, *win).items() if KERNEL in k)
    if not ns:
        return None
    least_s = max(work["flops"] / run.peaks["flops_per_s"],
                  work["bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ns / 1e9 / len(tr.ops))
