"""How far a sweep's summaries of one run lie from the reference run.

Each number is the smallest share eps of a natural scale that explains
every summary of a kind:

- a metric field (accuracy, test error): the final, mean and minimum may
  differ from the reference by eps * scale and the variance by
  eps * scale^2, where scale is the metric's value at the zero start
  (1 for accuracy, the test targets' mean square for the test error).
  The value at a clock budget, the clock at which a target is first met
  and the histogram quantiles jump by a whole iteration or bin when a
  value or a clock reading rounds across an edge, so they are judged
  against the reference's trajectory with every metric value moved by up
  to eps * scale and every clock reading by up to eps of itself;
- the clocks: the relative gap of the final simulated time and
  communication count.

A summary that is NaN, or lies outside the bounds even at eps = 1, reads 1.
"""

from __future__ import annotations

import numpy as np

from reference.lsq_admm import quantiles, time_to

WORST = 1.0


def _inside(v, lo, hi) -> bool:
    v = np.asarray(v, np.float64)
    return bool(np.all((v >= lo) & (v <= hi)))


def _steps_inside(dev: dict, ys, x, red: dict, f: str, scale: float, eps: float) -> bool:
    lo_y, hi_y = ys - eps * scale, ys + eps * scale
    if red.get("budgets"):
        B = np.asarray(red["budgets"], float)
        n = len(ys)
        first = np.clip(np.searchsorted(x * (1 + eps), B, "right") - 1, 0, n - 1)
        last = np.clip(np.searchsorted(x * (1 - eps), B, "right") - 1, 0, n - 1)
        lo = np.array([lo_y[a: c + 1].min() for a, c in zip(first, last)])
        hi = np.array([hi_y[a: c + 1].max() for a, c in zip(first, last)])
        if not _inside(dev[f"{f}/at_budget"], lo, hi):
            return False
    if red.get("targets"):
        early = time_to(lo_y, x, red["targets"]) * (1 - eps)
        late = time_to(hi_y, x, red["targets"]) * (1 + eps)
        if not _inside(dev[f"{f}/time_to"], early, late):
            return False
    if red.get("quantiles"):
        if not _inside(dev[f"{f}/quantiles"], quantiles(lo_y, red), quantiles(hi_y, red)):
            return False
    return True


def field_gap(dev: dict, ref: dict, tr: dict, red: dict, f: str, scale: float) -> float:
    """Smallest eps that explains every summary of metric field ``f``."""
    gap = 0.0
    for stat, s in (("final", scale), ("mean", scale), ("min", scale), ("var", scale * scale)):
        d = abs(float(dev[f"{f}/{stat}"]) - float(ref[f"{f}/{stat}"])) / s
        gap = max(gap, d if np.isfinite(d) else WORST)
    ys, x = np.asarray(tr[f], np.float64), np.asarray(tr[red["x"]], np.float64)
    if not _steps_inside(dev, ys, x, red, f, scale, WORST):
        return WORST
    if _steps_inside(dev, ys, x, red, f, scale, gap):
        return min(gap, WORST)
    lo, hi = max(gap, 1e-12), WORST  # bisect on a log scale
    for _ in range(60):
        mid = np.sqrt(lo * hi)
        if _steps_inside(dev, ys, x, red, f, scale, mid):
            hi = mid
        else:
            lo = mid
        if hi <= lo * (1 + 1e-3):
            break
    return float(hi)


def clock_gap(dev: dict, ref: dict) -> float:
    """Relative gap of the final simulated time and communication count."""
    gap = 0.0
    for key in ("sim_time/final", "comm_cost/final"):
        d = abs(float(dev[key]) - float(ref[key])) / abs(float(ref[key]))
        gap = max(gap, d if np.isfinite(d) else WORST)
    return min(gap, WORST)
