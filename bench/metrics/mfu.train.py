"""The whole training step's share of the chip's peak: the forward and
backward operations of the tokens the committing agent trains on in the
traced steps (``work.train_flops_per_token``, recomputation and the other
agent's gradient not counted) over the device time of the steps'
programs, against the bfloat16 peak (device trace)."""


def read(run):
    c, s = run.counters, run.summary
    if s is None or not c.get("flops") or not s["program_ns"]:
        return None
    return 100.0 * c["flops"] / run.peaks["flops_per_s"] / (s["program_ns"] / 1e9)
