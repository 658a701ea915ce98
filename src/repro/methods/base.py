"""MethodKernel protocol: one pure step function per algorithm (DESIGN.md §8).

Every consensus method in the repo — the paper's (c)sI-/I-ADMM, the §V-A
baselines (W-ADMM, D-ADMM, DGD, EXTRA), and the beyond-paper variants
(pI-ADMM, cq-sI-ADMM) — is expressed once, as a kernel with a single
``step`` function. Execution backends are *derived* from the kernel by
`repro.methods.driver`:

- serial:  ``lax.scan(step)`` over iterations, one run per dispatch;
- batched: ``vmap`` of the *same* scan over a leading runs axis, one jit
  trace per static-signature group, one device dispatch per memory
  chunk of it;
- sharded: ``shard_map`` of the batched scan over a 1-D device mesh on
  the runs axis — each device executes its local runs, bitwise equal to
  batched (DESIGN.md §9).

The contract that makes this work is the host/device split of DESIGN.md
§2: ``prepare`` samples everything random host-side (numpy) and returns
plain arrays; ``setup``/``init``/``step``/``final`` are pure jax functions
of those arrays, so stacking runs on a leading axis and vmapping is a
semantics-preserving transform (asserted elementwise in
``tests/test_methods.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.graph import Network
from repro.core.problems import LeastSquaresProblem

__all__ = [
    "Prepared",
    "TableRow",
    "as_table_row",
    "MethodKernel",
    "KERNELS",
    "register",
    "get_kernel",
]


@dataclasses.dataclass
class Prepared:
    """Host-side output of :meth:`MethodKernel.prepare` for ONE run.

    Attributes:
      consts: per-run constant arrays (data, targets, schedules' scalars).
        Stackable on a leading runs axis across a batch.
      steps: per-step input arrays, leading axis = iters (agent schedule,
        decode weights, step sizes, host-sampled noise). May be empty for
        methods whose iterations consume no per-step data (gossip).
      statics: hashable jit statics; must be identical across a batch
        (shapes, K, exact_x, iters, ...).
      max_statics: statics the batched driver takes as the ``max()`` of
        :meth:`MethodKernel.max_statics_bound` across runs (e.g. the
        masked window bound MU) — the corresponding runtime value lives
        in ``consts`` so runs with different values still share one
        trace (DESIGN.md §7, §8).
      comm: cumulative communication units per iteration, host accounting.
      sim_time: cumulative simulated seconds per iteration.
      decode_patterns: distinct alive patterns whose coded decode the
        host solved for this run (0 for methods without one); the
        prepare span of the batched driver sums it (DESIGN.md §16).
    """

    consts: Tuple[np.ndarray, ...]
    steps: Tuple[np.ndarray, ...]
    statics: Dict[str, object]
    max_statics: Dict[str, int]
    comm: np.ndarray
    sim_time: np.ndarray
    decode_patterns: int = 0


class TableRow(NamedTuple):
    """One run's row of a const table that the runs of a batch share: the
    whole ``table`` (U, ...) and the run's ``row`` of it. The batched
    driver hands a shared const in this form, in place of the run's own
    copy, at the positions a kernel lists in `MethodKernel.row_consts`:
    the step then reads the table itself, and no per-run copy is taken
    before the scan."""

    table: Any
    row: Any


def as_table_row(const) -> TableRow:
    """``const`` as a `TableRow`: itself, or a run's own array as the one
    row of a table."""
    if isinstance(const, TableRow):
        return const
    return TableRow(const[None], 0)


class MethodKernel:
    """One algorithm = one ``step`` function plus host-side preparation.

    Subclasses implement:

    - ``config(case)``: build the method-specific config from a duck-typed
      `repro.experiments.sweep.Case` (any object with the right fields).
    - ``static_signature(problem, cfg, iters)``: hashable key of everything
      forcing a fresh jit trace; equal keys batch into one dispatch.
    - ``prepare(problem, net, cfg, iters) -> Prepared``: host-side numpy.
    - ``setup(consts, statics) -> aux``: in-jit, once per run — derived
      constants (Gram matrices, flat views, solve operators).
    - ``init(aux, statics) -> state``: initial scan carry (a dict pytree).
    - ``step(state, inp, aux, statics) -> (state, (acc, test_err, z_err))``:
      ONE iteration; ``inp`` is the per-step slice of ``Prepared.steps``.
    - ``final(state, aux, statics) -> (x, z)``: per-agent iterates (N, p, d)
      and the consensus model (p, d).
    """

    name: str = "?"
    # Positions of `Prepared.consts` that `setup` takes as `TableRow`s
    # where the runs of a batch share all of them, else as the runs' own
    # arrays (`as_table_row` reads both).
    row_consts: Tuple[int, ...] = ()

    def config(self, case):
        raise NotImplementedError

    def static_signature(
        self, problem: LeastSquaresProblem, cfg, iters: int
    ) -> tuple:
        """Hashable key of everything forcing a fresh jit trace.

        Convention: variant execution modes extend the family's base
        tuple with a tagged suffix rather than replacing it — async runs
        append ``("async", staleness_cap)`` (DESIGN.md §13), adaptive
        controller runs append ``("adaptive", n_arms, algo)``
        (DESIGN.md §15). Suffixes keep base grids batching exactly as
        before while guaranteeing a variant run never merges into a
        group whose kernel would mis-build its config.
        """
        raise NotImplementedError

    def prepare(
        self,
        problem: LeastSquaresProblem,
        net: Network,
        cfg,
        iters: int,
    ) -> Prepared:
        raise NotImplementedError

    def max_statics_bound(
        self, problem: LeastSquaresProblem, cfg, iters: int
    ) -> Dict[str, int]:
        """Exact bound on :attr:`Prepared.max_statics` WITHOUT preparing.

        The batched driver prepares runs per memory chunk (DESIGN.md §9),
        so the group's jit statics must be known up front from (problem,
        cfg) alone — preparing every run first would cost the very
        O(R x iters) host memory chunking exists to avoid. Kernels whose
        ``prepare`` emits ``max_statics`` must override this with a value
        >= every run's prepared value (equal keys); the driver verifies
        each prepared run against it, and compiles for the bound, so an
        exact bound compiles what the runs need. Kernels with empty
        ``max_statics`` inherit this default.
        """
        return {}

    def setup(self, consts, statics):
        return consts

    def init(self, aux, statics):
        raise NotImplementedError

    def step(self, state, inp, aux, statics):
        raise NotImplementedError

    def final(self, state, aux, statics):
        raise NotImplementedError

    # -- shared aux/state/metric plumbing ----------------------------------

    @staticmethod
    def lsq_aux(O, T, x_star, O_test, T_test):
        """Aux base for kernels that keep the raw (N, b, ...) data views:
        everything :meth:`metrics` consumes plus shape/dtype bookkeeping."""
        N, b, p = O.shape
        return dict(
            O=O, T=T, b=b,
            x_star=x_star,
            xs_norm=jnp.linalg.norm(x_star),
            O_test=O_test, T_test=T_test,
            shape=(N, p, T.shape[2]), dtype=O.dtype,
        )

    @staticmethod
    def xyz_state(aux):
        """Zero-initialized (x, y, z) carry of the incremental-ADMM family."""
        N, p, d = aux["shape"]
        zeros = jnp.zeros((N, p, d), aux["dtype"])
        return dict(x=zeros, y=zeros, z=jnp.zeros((p, d), aux["dtype"]))

    # -- shared metric algebra (eq. 23 accuracy, test MSE, z error) --------

    @staticmethod
    def metrics(x, z, aux):
        """Standard per-step metrics from aux['x_star']/test operands."""
        x_star, xs_norm = aux["x_star"], aux["xs_norm"]
        N = x.shape[0]
        acc = jnp.mean(
            jnp.linalg.norm((x - x_star[None]).reshape(N, -1), axis=1)
            / jnp.maximum(xs_norm, 1e-12)
        )
        if "Gt" in aux:
            # ||O z - T||^2 / n = (z'Gz - 2<z,C> + ||T||^2) / n via the test
            # set's precomputed Gram/cross matrices (p x p per step).
            test_err = (
                jnp.einsum("pd,pq,qd->", z, aux["Gt"], z)
                - 2.0 * jnp.vdot(z, aux["Ct"])
                + aux["TTt"]
            ) / aux["n_test"]
        else:
            r = aux["O_test"] @ z - aux["T_test"]
            test_err = jnp.mean(jnp.sum(r * r, axis=-1))
        z_err = jnp.linalg.norm(z - x_star) / jnp.maximum(xs_norm, 1e-12)
        return acc, test_err, z_err


KERNELS: Dict[str, MethodKernel] = {}


def register(kernel: MethodKernel, *names: str) -> MethodKernel:
    """Add a kernel to the method registry (name -> singleton instance).

    Extra ``names`` register the SAME instance under several method
    names (sI-/csI-/I-ADMM are one kernel whose behavior is fully
    determined by the run config), so they share jit caches and batch
    into one dispatch when shapes allow.
    """
    for name in names or (kernel.name,):
        if name in KERNELS:
            raise ValueError(f"duplicate method kernel {name!r}")
        KERNELS[name] = kernel
    return kernel


def get_kernel(name: str) -> MethodKernel:
    if name not in KERNELS:
        raise KeyError(
            f"unknown method {name!r}; known: {sorted(KERNELS)}"
        )
    return KERNELS[name]
