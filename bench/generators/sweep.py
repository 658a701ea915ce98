"""Sweep traffic: jobs of one grid of least-squares runs, one after another.

A traffic file of this kind names the grid's axes (each a list of field
overrides), the seeds per job, the iterations per run, the execution
tier and the streaming summaries. Job j of a run started with ``--seed s``
holds every grid point crossed with the case seeds
``s * SEED_STRIDE + j * JOB_STRIDE + i``, i < seeds per job: the same
sizes and arrivals for every seed, fresh fleets for every job. Job 0 is
the warm-up; the window runs jobs 1, 2, ...

The program is entered at ``repro.experiments.sweep.run_sweep`` with the
job's cases and the summaries as ``reductions``; a job ends when its
summaries are on the host. The check recomputes a sample of the window's
runs, drawn from the seed, with the plain reference
(`reference.lsq_admm`) and reads how far each kind of summary lies from
it (`compare`).
"""

from __future__ import annotations

import itertools
import math
from typing import Dict, List

import numpy as np

import work
from compare import clock_gap, field_gap
from reference import lsq_admm

SEED_STRIDE = 1 << 12
JOB_STRIDE = 64
# Summaries that are finite in every sound run (a target's clock is inf
# when the run never meets it).
REQUIRED_FINITE = ("final", "mean", "var", "min", "at_budget", "quantiles")


def _tuples(d: dict) -> dict:
    return {k: tuple(v) if isinstance(v, list) else v for k, v in d.items()}


def job_cases(config: dict, traffic: dict, seed: int, job: int) -> List[dict]:
    """Every grid point crossed with the job's case seeds, seed innermost."""
    n = traffic["seeds_per_job"]
    if n > JOB_STRIDE or not 0 <= job < SEED_STRIDE // JOB_STRIDE:
        raise ValueError(f"job {job} of {n} seeds does not fit the seed layout")
    base = dict(config["case"], iters=traffic["iters"])
    out = []
    for combo in itertools.product(*(vals for _, vals in traffic["axes"])):
        point = dict(base)
        for override in combo:
            point.update(override)
        for i in range(n):
            out.append(dict(point, seed=seed * SEED_STRIDE + job * JOB_STRIDE + i))
    return out


def run_work(config: dict, case: dict, red: dict) -> tuple:
    """(flops, bytes) that one run of ``case`` needs (`work`)."""
    ds = config["dataset"]
    mu = case["M"] // (case["S"] + 1) // case["K"]
    summaries = 4 + len(red.get("budgets", ())) + len(red.get("targets", ())) + 1
    f, b = work.admm_run_iteration(
        case["N"], case["K"], mu, ds["p"], ds["d"], len(red["fields"]), summaries,
        itemsize=np.dtype(config["dtype"]).itemsize,
    )
    return f * case["iters"], b * case["iters"]


class Workload:
    def __init__(self, config: dict, traffic: dict, seed: int):
        # The program under test, entered here and nowhere else.
        from repro.experiments.sweep import Case, run_sweep
        from repro.methods import Reduction

        self._case, self._run_sweep = Case, run_sweep
        self.config, self.traffic, self.seed = config, traffic, seed
        self.red = traffic["reduction"]
        self.reduction = Reduction(**_tuples(self.red))

    def _job(self, job: int) -> dict:
        cases = job_cases(self.config, self.traffic, self.seed, job)
        res = self._run_sweep(
            [self._case(**_tuples(c)) for c in cases],
            mode=self.traffic["mode"], reductions=self.reduction,
        )
        return {"cases": cases, "reduced": res.reduced}

    def warm_up(self) -> None:
        self._job(0)

    def step(self, i: int) -> dict:
        """Job i + 1; returns when its summaries are on the host."""
        return self._job(i + 1)

    def finish(self) -> None:
        """Nothing is left on the device after a job."""

    def counters(self, records: List[dict]) -> dict:
        """Runs, run-iterations, and the work they need (`work`)."""
        cases = [c for rec in records for c in rec["cases"]]
        flops = nbytes = 0
        for c in cases:
            f, b = run_work(self.config, c, self.red)
            flops, nbytes = flops + f, nbytes + b
        ds = self.config["dataset"]
        run_iters = sum(c["iters"] for c in cases)
        return {
            "runs": len(cases),
            "run_iters": run_iters,
            "flops": flops,
            "bytes": nbytes,
            "coded_admm_update": {
                "J": self.config["case"]["K"], "n": ds["p"] * ds["d"], "calls": run_iters,
            },
        }

    # -- correctness -------------------------------------------------------

    def failed(self, records: List[dict]) -> int:
        """Runs with a summary that has to be finite and is not (the
        clock at which a target is met is inf when it never is)."""
        bad = 0
        for rec in records:
            ok = np.ones(len(rec["cases"]), dtype=bool)
            for key, v in rec["reduced"].items():
                if key.rsplit("/", 1)[-1] in REQUIRED_FINITE:
                    v = np.asarray(v).reshape(len(ok), -1)
                    ok &= np.isfinite(v).all(axis=1)
            bad += int((~ok).sum())
        return bad

    def _samples(self, n_jobs: int):
        """(job index, run index) of the runs the check recomputes: each
        grid point ``runs_per_grid_point`` times, job and seed drawn from
        the run's seed."""
        n = self.traffic["seeds_per_job"]
        points = math.prod(len(vals) for _, vals in self.traffic["axes"])
        rng = np.random.default_rng([self.seed, 17])
        for g in range(points):
            for _ in range(self.traffic["check"]["runs_per_grid_point"]):
                yield int(rng.integers(n_jobs)), g * n + int(rng.integers(n))

    def _gaps(self, pairs) -> Dict[str, dict]:
        """Largest gap of each kind over (case, summaries) pairs, beside
        the traffic file's limits."""
        limits = self.traffic["check"]["limits"]
        worst = {name: 0.0 for name in limits}
        for case, dev in pairs:
            tr = lsq_admm.run(self.config, case, case["iters"])
            ref = lsq_admm.summarize(tr, self.red)
            scale = {"accuracy": 1.0, "test_error": tr["test_scale"]}
            for f in self.red["fields"]:
                worst[f] = max(worst[f], field_gap(dev, ref, tr, self.red, f, scale[f]))
            worst["clock"] = max(worst["clock"], clock_gap(dev, ref))
        return {k: {"value": worst[k], "limit": limits[k]} for k in limits}

    def check(self, records: List[dict]) -> Dict[str, dict]:
        """The window's sampled runs against the plain reference."""
        def pairs():
            for j, r in self._samples(len(records)):
                rec = records[j]
                yield rec["cases"][r], {k: v[r] for k, v in rec["reduced"].items()}

        return self._gaps(pairs())

    def control(self, dtype: str) -> Dict[str, dict]:
        """The same check with the reference computed in ``dtype`` in the
        program's place, on the runs the first window job would sample."""
        cases = job_cases(self.config, self.traffic, self.seed, 1)

        def pairs():
            for _, r in self._samples(1):
                tr = lsq_admm.run(self.config, cases[r], cases[r]["iters"], dtype)
                yield cases[r], lsq_admm.summarize(tr, self.red)

        return self._gaps(pairs())
