"""The whole sweep step's share of the chip's peak: the operations and
bytes that the traced jobs' run-iterations need (``work.admm_run_iteration``)
over the device time of the jobs' programs, against the peak that binds.
At least-squares widths the bytes bind, so this is the share of peak HBM
bandwidth while the device runs the sweep (device trace)."""


def read(run):
    c, p, s = run.counters, run.peaks, run.summary
    if s is None or not c.get("run_iters") or not s["program_ns"]:
        return None
    least_s = max(c["flops"] / p["flops_per_s"], c["bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least_s / (s["program_ns"] / 1e9)
