"""Grid/axes spec -> batched vmapped run -> per-case traces (DESIGN.md §7).

A `Case` pins down ONE run completely: method, dataset, topology, ADMM
hyper-parameters, straggler model, and seed. A `SweepSpec` is a base case
plus named axes; its Cartesian expansion is the grid. `run_sweep` groups
the grid by jit *static signature* (everything that would force a fresh
trace: shapes, K, P, exact_x, iters, method kernel — see
`MethodKernel.static_signature`, DESIGN.md §8) and executes each group
as one `jax.vmap`-ed `lax.scan` — one compile per group, however many
(seed, config) pairs it contains, and one device dispatch per chunk of
the per-device memory budget (one unless the group outgrows it). With
more than one visible device the vmapped runs axis is additionally laid
out over a 1-D mesh (`repro.methods.driver.run_sharded`, DESIGN.md §9);
the tier is picked by ``mode`` ("auto"/"serial"/"batched"/"sharded").
Host-side sampling (topology, data allocation, straggler times, decode
vectors) stays per-run and is stacked into the batched scan's per-step
inputs.
Each call names its host phases (materialize, prepare, stack, transfer,
execute) with profiler spans, which exist only while a profile is being
captured (DESIGN.md §16).

Timing of the serial-vs-batched paths is recorded in EXPERIMENTS.md §Perf.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.compile_cache import enable_compilation_cache
from repro.core.admm import ADMMConfig, Trace
from repro.core.graph import Network, make_network
from repro.core.problems import DATASETS, LeastSquaresProblem, allocate
from repro.core.timing import TimingModel
from repro.methods import (
    KERNELS,
    Reduction,
    get_kernel,
    run_batch,
    run_serial,
    run_sharded,
)

MODES = ("auto", "serial", "batched", "sharded")

__all__ = ["Case", "SweepSpec", "SweepResult", "run_sweep"]

# Every registered method kernel is sweepable (DESIGN.md §8).
METHODS = tuple(KERNELS)


@dataclasses.dataclass(frozen=True)
class Case:
    """One fully-specified experiment run (hashable, so grids dedupe)."""

    method: str = "sI-ADMM"  # one of METHODS
    dataset: str = "usps"  # key of repro.core.problems.DATASETS
    N: int = 10  # agents
    K: int = 3  # ECNs per agent
    connectivity: float = 0.5  # eta of make_network
    seed: int = 0  # drives topology, data AND schedule sampling
    iters: int = 1000
    # (c)sI-ADMM hyper-parameters (paper §V defaults)
    rho: float = 1.0
    c_tau: float = 0.5
    c_gamma: float = 1.0
    M: int = 60
    S: int = 0
    scheme: str = "uncoded"
    traversal: str = "hamiltonian"
    # gossip/first-order baseline knobs
    alpha: float = 0.05  # DGD/EXTRA step size; D-ADMM uses `rho`
    # pI-ADMM (privacy) knob
    sigma: float = 0.01  # primal perturbation std at k=1
    # cq-sI-ADMM (compressed token) knobs
    compressor: str = "topk"  # "topk" | "quant"
    frac: float = 0.25  # topk: fraction of token entries kept
    bits: int = 8  # quant: bits per transmitted entry
    # timing model (defaults mirror TimingModel so engine runs match
    # run_incremental_admm(..., straggler=None) if core defaults move)
    p_straggle: float = TimingModel.p_straggle
    delay: float = TimingModel.delay
    epsilon: float = TimingModel.epsilon
    # heterogeneous fleet (DESIGN.md §10): per-worker speed-class factors
    # (assigned round-robin) and the base response distribution
    speed_classes: Tuple[float, ...] = TimingModel.speed_classes
    response: str = TimingModel.response
    # decode deadline for partial-recovery code families (DESIGN.md §11)
    deadline: Optional[float] = TimingModel.deadline
    # event-driven mode (DESIGN.md §13): staleness bound + churn process
    tau_max: float = TimingModel.tau_max
    churn_rate: float = TimingModel.churn_rate
    mttr: float = TimingModel.mttr
    staleness_cap: int = TimingModel.staleness_cap
    # a-csI-ADMM online controller (DESIGN.md §15): the registered arm
    # set — (scheme, S, deadline) frontier cells as a hashable tuple of
    # triples — and the bandit policy selecting among them per step
    arms: Tuple[Tuple[str, int, Optional[float]], ...] = ()
    bandit: str = "ucb1"  # "ucb1" | "exp3"
    bandit_c: float = 0.5  # UCB1 confidence width
    bandit_eta: float = 0.1  # EXP3 learning rate
    bandit_gamma: float = 0.1  # EXP3 exploration mixture

    def admm_config(self) -> ADMMConfig:
        return ADMMConfig(
            rho=self.rho,
            c_tau=self.c_tau,
            c_gamma=self.c_gamma,
            M=self.M,
            K=self.K,
            S=self.S,
            scheme=self.scheme,
            exact_x=self.method == "I-ADMM",
            traversal=self.traversal,
            seed=self.seed,
        )

    def timing_model(self) -> TimingModel:
        return TimingModel(
            p_straggle=self.p_straggle,
            delay=self.delay,
            epsilon=self.epsilon,
            speed_classes=self.speed_classes,
            response=self.response,
            deadline=self.deadline,
            tau_max=self.tau_max,
            churn_rate=self.churn_rate,
            mttr=self.mttr,
            staleness_cap=self.staleness_cap,
        )

    def label(self, *fields: str) -> str:
        """Compact row label, e.g. ``csI-ADMM[S=2,seed=1]``."""
        if not fields:
            fields = ("dataset", "seed")
        kv = ",".join(f"{f}={getattr(self, f)}" for f in fields)
        return f"{self.method}[{kv}]"


@dataclasses.dataclass(frozen=True)
class SweepSpec:
    """Base case + named axes = a Cartesian experiment grid.

    Axis values are either plain field values (axis name = field name) or
    dicts of several field overrides applied together (axis name is just a
    label), e.g.::

        SweepSpec("fig5", Case(dataset="synthetic", K=6, M=360),
                  axes={"S": [0, 1, 2, 3], "seed": range(4)},
                  fixup=lambda c: dataclasses.replace(
                      c, scheme="cyclic" if c.S else "uncoded"))
    """

    name: str
    base: Case
    axes: Mapping[str, Sequence] = dataclasses.field(default_factory=dict)
    fixup: Optional[Callable[[Case], Case]] = None
    description: str = ""
    # Evaluation axis of the sweep's headline reduction: None = iteration
    # index, or a cumulative Trace field ("sim_time"/"comm_cost") that
    # `reduce_mean`/`emit_rows` resample runs onto (DESIGN.md §10).
    x_axis: Optional[str] = None
    # Streaming in-scan reductions (DESIGN.md §12): when set, run_sweep
    # folds these fixed-size summaries into the scan carry instead of
    # materializing per-iteration Traces — memory O(grid), the fleet-
    # scale path. None keeps the full-Trace default.
    reductions: Optional[Reduction] = None

    def cases(self) -> List[Case]:
        names = list(self.axes)
        cases: List[Case] = []
        seen = set()
        for combo in itertools.product(*(self.axes[n] for n in names)):
            c = self.base
            for name, value in zip(names, combo):
                if isinstance(value, dict):
                    c = dataclasses.replace(c, **value)
                else:
                    c = dataclasses.replace(c, **{name: value})
            if self.fixup is not None:
                c = self.fixup(c)
            if c not in seen:  # fixups may merge grid points; dedupe
                seen.add(c)
                cases.append(c)
        return cases


@dataclasses.dataclass
class SweepResult:
    """Per-case traces + how the grid was batched onto the device(s)."""

    cases: List[Case]
    traces: List[Trace]
    groups: List[Tuple[tuple, int]]  # (static signature, n_runs) per group
    wall_s: float
    mode: str = "batched"  # resolved execution tier (DESIGN.md §9)
    n_devices: int = 1
    # Streaming-sweep output (DESIGN.md §12): flat summary dict keyed
    # "{field}/{stat}", each value a (n_cases, ...) array in grid order.
    # Exactly one of ``traces`` / ``reduced`` is populated.
    reduced: Optional[Dict[str, np.ndarray]] = None

    @property
    def n_dispatches(self) -> int:
        return len(self.groups)

    def trace(self, **filters) -> Trace:
        hits = [
            t
            for c, t in zip(self.cases, self.traces)
            if all(getattr(c, k) == v for k, v in filters.items())
        ]
        if len(hits) != 1:
            raise KeyError(f"{filters} matched {len(hits)} cases, want 1")
        return hits[0]

    def select(self, **filters) -> List[Tuple[Case, Trace]]:
        return [
            (c, t)
            for c, t in zip(self.cases, self.traces)
            if all(getattr(c, k) == v for k, v in filters.items())
        ]


# --------------------------------------------------------------------------
# Case materialization (host-side, cached within one run_sweep call)
# --------------------------------------------------------------------------


def _materialize(
    case: Case,
    net_cache: Dict[tuple, Network],
    prob_cache: Dict[tuple, LeastSquaresProblem],
) -> Tuple[Network, LeastSquaresProblem]:
    if case.dataset not in DATASETS:
        raise KeyError(
            f"unknown dataset {case.dataset!r}; known: {list(DATASETS)}"
        )
    nkey = (case.N, case.connectivity, case.seed)
    net = net_cache.get(nkey)
    if net is None:
        net = net_cache[nkey] = make_network(
            case.N, case.connectivity, seed=case.seed
        )
    pkey = (case.dataset, case.seed, case.N, case.K)
    prob = prob_cache.get(pkey)
    if prob is None:
        prob = prob_cache[pkey] = allocate(
            DATASETS[case.dataset](case.seed), case.N, case.K
        )
    return net, prob


def _signature(case: Case, prob: LeastSquaresProblem) -> tuple:
    """Everything that forces a fresh jit trace: the kernel's static key."""
    kernel = get_kernel(case.method)
    return kernel.static_signature(prob, kernel.config(case), case.iters)


def _dispatch_group(
    method: str,
    cases: List[Case],
    nets: List[Network],
    probs: List[LeastSquaresProblem],
    mode: str,
    reductions: Optional[Reduction] = None,
):
    """Registry lookup + the derived execution backend (DESIGN.md §8, §9).

    Returns the group's per-run `Trace`s — or, with ``reductions``, one
    dict of (group_size, ...) summary arrays (serial runs are stacked
    host-side to the same shape)."""
    kernel = get_kernel(method)
    iters = cases[0].iters
    cfgs = [kernel.config(c) for c in cases]
    if mode == "serial":
        runs = [
            run_serial(kernel, p, n, cf, iters, reductions=reductions)
            for p, n, cf in zip(probs, nets, cfgs)
        ]
        if reductions is None:
            return runs
        return {k: np.stack([r[k] for r in runs]) for k in runs[0]}
    if mode == "sharded":
        return run_sharded(
            kernel, probs, nets, cfgs, iters, reductions=reductions
        )
    return run_batch(kernel, probs, nets, cfgs, iters, reductions=reductions)


def _resolve_mode(mode: str) -> str:
    """Execution-tier resolution (DESIGN.md §9): ``auto`` picks sharded
    iff >1 device is visible."""
    if mode not in MODES:
        raise ValueError(f"unknown sweep mode {mode!r}; known: {MODES}")
    if mode == "auto":
        mode = "sharded" if len(jax.devices()) > 1 else "batched"
    return mode


def run_sweep(
    spec_or_cases,
    *,
    mode: str = "auto",
    verbose: bool = False,
    reductions: Optional[Reduction] = None,
) -> SweepResult:
    """Execute a sweep: one vmapped dispatch per static-signature group.

    Args:
      spec_or_cases: a `SweepSpec` or an explicit list of `Case`s.
      mode: execution tier — "serial" (each case through the per-run
        entry points: the reference path for correctness tests and the
        "before" column of the EXPERIMENTS.md §Perf timing table),
        "batched" (single-device vmap), "sharded" (the same vmap laid out
        over a device mesh on the runs axis, DESIGN.md §9), or "auto"
        (the default: sharded iff >1 device is visible).
      verbose: print one line per dispatched group.
      reductions: a `Reduction` to fold in-scan instead of materializing
        Traces (DESIGN.md §12); defaults to the spec's own ``reductions``
        declaration when a `SweepSpec` is passed. The result then carries
        ``reduced`` (grid-shaped summary arrays) and an empty ``traces``.

    Returns a `SweepResult` with traces (or reduced summaries) in the
    original grid order.
    """
    if reductions is None and isinstance(spec_or_cases, SweepSpec):
        reductions = spec_or_cases.reductions
    cases = (
        spec_or_cases.cases()
        if isinstance(spec_or_cases, SweepSpec)
        else list(spec_or_cases)
    )
    if not cases:
        raise ValueError("empty sweep")
    mode = _resolve_mode(mode)
    enable_compilation_cache()

    with jax.profiler.TraceAnnotation("repro.sweep", runs=len(cases)):
        t0 = time.perf_counter()
        net_cache: Dict[tuple, Network] = {}
        prob_cache: Dict[tuple, LeastSquaresProblem] = {}
        with jax.profiler.TraceAnnotation("repro.sweep.materialize"):
            mats = [_materialize(c, net_cache, prob_cache) for c in cases]

        # Group by static signature, preserving first-seen order.
        groups: Dict[tuple, List[int]] = {}
        for idx, (case, (_net, prob)) in enumerate(zip(cases, mats)):
            groups.setdefault(_signature(case, prob), []).append(idx)

        traces: List[Optional[Trace]] = [None] * len(cases)
        rows: List[Optional[dict]] = [None] * len(cases)
        group_meta: List[Tuple[tuple, int]] = []
        for sig, idxs in groups.items():
            gcases = [cases[i] for i in idxs]
            gnets = [mats[i][0] for i in idxs]
            gprobs = [mats[i][1] for i in idxs]
            if verbose:
                print(
                    f"[sweep] {sig[0]} group x{len(idxs)} ({mode}): {sig[1:]}"
                )
            gout = _dispatch_group(
                gcases[0].method, gcases, gnets, gprobs, mode,
                reductions=reductions,
            )
            if reductions is not None:
                # Scatter the group's (group_size, ...) summary arrays back
                # into grid order; stacked once below.
                for j, i in enumerate(idxs):
                    rows[i] = {k: v[j] for k, v in gout.items()}
            else:
                for i, tr in zip(idxs, gout):
                    traces[i] = tr
            group_meta.append((sig, len(idxs)))

        reduced = None
        if reductions is not None:
            keys = rows[0].keys()
            if any(r.keys() != keys for r in rows[1:]):
                raise ValueError(
                    "sweep groups produced different reduction keys; all "
                    "groups must share one Reduction spec"
                )
            reduced = {k: np.stack([r[k] for r in rows]) for k in keys}
            traces = []

        return SweepResult(
            cases=cases,
            traces=traces,  # type: ignore[arg-type]
            groups=group_meta,
            wall_s=time.perf_counter() - t0,
            mode=mode,
            n_devices=len(jax.devices()),
            reduced=reduced,
        )
