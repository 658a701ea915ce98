"""Incremental (c)sI-/I-ADMM as a MethodKernel (paper Algorithms 1 & 2).

The ONE step implementation for the whole ADMM family (DESIGN.md §8): the
zero-weight-masked scan body that previously existed twice (a serial
`dynamic_slice` variant and a masked batched clone) is now the canonical
kernel, executed serially or vmapped by `repro.methods.driver`.

Per step (active agent i = i_k, eqs. 5a/5b/4c):

  x_i^{k+1} = (tau^k x_i^k + rho z^k + y_i^k - G_i) / (rho + tau^k)
  y_i^{k+1} = y_i^k + rho gamma^k (z^k - x_i^{k+1})
  z^{k+1}   = z^k + [ (x_i^{k+1}-x_i^k) - (y_i^{k+1}-y_i^k)/rho ] / N

with G_i the decoded mini-batch gradient (eq. 6). The coded
encode->decode path collapses host-side to per-partition weights
w = (a^T B)/K; the device step computes one masked sub-batch gradient
message per ECN partition and hands decode-combine + eq. (5a) to the
fused Pallas kernel `repro.kernels.ops.coded_admm_update` (interpret
mode off-TPU), so serial, batched, and mesh-sharded execution all
exercise the same fused hot path (DESIGN.md §5, §9). The sub-batch
size mu = M/((S+1)K) is a *runtime* input masked against the static
bound MU, which is what lets a whole straggler-tolerance sweep share
one jit trace (DESIGN.md §7). I-ADMM (exact_x) replaces the stochastic
x-update with the closed-form full-batch solve (eq. 4a).

Subclass hooks ``_perturb_x`` (pI-ADMM, `repro.methods.privacy`),
``_token_increment`` (cq-sI-ADMM, `repro.methods.compression`) and
``_select_arm`` (a-csI-ADMM, `repro.control.kernel`) extend the family
without touching the drivers. ``_select_arm`` runs FIRST: an adaptive
subclass stacks every arm's per-step schedule on an extra axis and the
hook resolves the carry-resident controller state into this iteration's
live row, handing the base step a pseudo-``inp`` with the standard
layout — the base algebra never learns arms exist (DESIGN.md §15).

Event-driven mode (DESIGN.md §13): when the run's `TimingModel` is
async (``tau_max > 0`` or ``churn_rate > 0``) the token increment dz of
iteration k lands with a bounded simulated delay instead of
immediately. The kernel carries a ``pend`` ring buffer of
``staleness_cap`` in-flight increments; host-precomputed write/read
slots and the activity gate ride as THREE per-step arrays appended
AFTER every subclass extra (read via negative indices, so the
privacy/compression hooks' positional inputs are untouched). Skipped
activations (crashed agent, undecodable churned pattern —
`repro.core.admm.make_schedule`) gate x/y/dz to exact zeros. The sync
path (``tau_max = 0``, ``churn_rate = 0``) takes the EXACT pre-async
code — same statics, same steps, same jit trace — so synchronous runs
stay bit-identical.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.admm import ADMMConfig, make_schedule
from repro.core.coding import GradientCode, make_code
from repro.core.graph import Network
from repro.core.problems import LeastSquaresProblem
from repro.core.timing import TimingModel
from repro.kernels.ops import coded_admm_update, fit_block_n

from .base import (
    MethodKernel,
    Prepared,
    TableRow,
    as_table_row,
    register,
)

__all__ = ["ADMMRun", "IncrementalADMM", "ADMM_KERNEL"]


@dataclasses.dataclass(frozen=True)
class ADMMRun:
    """Per-run config of the ADMM family: hyper-params + timing model."""

    cfg: ADMMConfig
    timing: Optional[TimingModel] = None
    code: Optional[GradientCode] = None


class IncrementalADMM(MethodKernel):
    """sI-ADMM / csI-ADMM / I-ADMM (ONE kernel, three registry names).

    The behavioral switches (exact_x, scheme, S) all live in the
    `ADMMConfig`, so a single instance serves all three paper names and
    ``name`` is the family tag — mixed sI/csI grids with equal shapes
    share a static signature and batch into one dispatch, exactly like
    the pre-refactor family key."""

    name = "admm"
    row_consts = (0, 1)  # O and T: each step reads K windows of the table

    # -- host side ---------------------------------------------------------

    def config(self, case) -> ADMMRun:
        return ADMMRun(case.admm_config(), case.timing_model())

    def static_signature(
        self, problem: LeastSquaresProblem, run: ADMMRun, iters: int
    ) -> tuple:
        cfg = run.cfg
        sig = (
            self.name,
            problem.N, problem.b, problem.p, problem.d,
            problem.O_test.shape[0],
            cfg.K, problem.b // cfg.K, cfg.exact_x, iters,
        )
        if run.timing is not None and run.timing.is_async:
            # Async runs carry the pend ring + extra step inputs: their
            # own trace, one dispatch group per ring depth (DESIGN.md
            # §13). Sync runs keep the exact pre-async signature.
            sig += ("async", run.timing.staleness_cap)
        return sig

    def prepare(
        self,
        problem: LeastSquaresProblem,
        net: Network,
        run: ADMMRun,
        iters: int,
    ) -> Prepared:
        cfg = run.cfg
        cfg.validate()
        timing = run.timing or TimingModel()
        code = run.code or make_code(cfg.scheme, cfg.K, cfg.S, seed=cfg.seed)
        if code.K != cfg.K or code.S != cfg.S:
            raise ValueError("code does not match config (K, S)")

        sched = make_schedule(cfg, net, code, timing, iters, problem.b)
        dt = problem.O.dtype
        # Encode->decode folds to per-partition weights host-side: the
        # decoded mini-batch gradient (eq. 6) is
        #   G = (1/K) sum_j a_j sum_t B[j,t] g~_t = sum_t w_t g~_t.
        W_steps = (sched["decode"].astype(dt) @ code.B.astype(dt)) / cfg.K
        # Runtime live-partition mask for the fused kernel (DESIGN.md
        # §11): partition t is live iff some alive ECN covers it, so the
        # kernel hard-zeroes the rest independently of the folded
        # weights. Exact-decode-at-R vs approximate-decode-at-deadline
        # is already selected per iteration by `make_schedule` — the
        # mask and coefficients are per-step DATA, so every deadline
        # pattern shares one jit trace.
        cover = np.abs(code.B) > 1e-12  # (K ecn, K partition)
        wmask = (sched["alive"].astype(dt) @ cover.astype(dt)) > 0
        # One token hop per activation; response + link time per iter.
        # Compressed tokens (cq-sI-ADMM) ship fewer bits, so their
        # hop's link time scales by the same true bit cost the
        # communication accounting charges (DESIGN.md §10).
        sim_time = np.cumsum(
            sched["resp_time"]
            + sched["link_time"] * self._comm_per_iter(run, problem)
        )
        steps = self._extra_steps(
            run, problem, iters,
            (
                sched["agents"],
                sched["offsets"],
                W_steps,
                sched["tau"].astype(dt),
                sched["gamma"].astype(dt),
                wmask.astype(dt),
            ),
        )
        statics = self._statics(run, problem, iters, sched)
        if timing.is_async:
            # Event-driven mode (DESIGN.md §13): write/read ring slots +
            # activity gate append AFTER subclass extras — the step reads
            # them via negative indices, so hook inputs keep their
            # positions. Staleness is sampled on the run's own clock
            # (stream [7, seed]); delay d in [0, D-1] steps lands the
            # increment written at iteration k at the end of iteration
            # k + d (d = 0 is the synchronous landing).
            D = timing.staleness_cap
            delta = timing.staleness_steps(
                sim_time, np.random.default_rng([7, cfg.seed])
            )
            k = np.arange(iters)
            steps = steps + (
                ((k + delta) % D).astype(np.int32),
                (k % D).astype(np.int32),
                sched["act"].astype(dt),
            )
            statics = dict(statics, ASYNC=True, D=D)
        return Prepared(
            consts=(
                problem.O,
                problem.T,
                problem.x_star().astype(dt),
                problem.O_test,
                problem.T_test,
                np.asarray(cfg.rho, dtype=dt),
                np.asarray(sched["mu"], dtype=np.int32),
            ),
            steps=steps,
            statics=statics,
            max_statics=dict(MU=int(sched["mu"])),
            comm=np.cumsum(np.full(iters, self._comm_per_iter(run, problem))),
            sim_time=sim_time,
            decode_patterns=sched["decode_patterns"],
        )

    def max_statics_bound(
        self, problem: LeastSquaresProblem, run: ADMMRun, iters: int
    ) -> dict:
        # Exact: make_schedule's mu IS M_bar // K (no sampling involved),
        # so every chunk of a group shares one jit trace.
        return dict(MU=run.cfg.M_bar // run.cfg.K)

    def _statics(self, run: ADMMRun, problem, iters, sched) -> dict:
        return dict(
            name=self.name, iters=iters, P=sched["P"], K=run.cfg.K,
            N=problem.N, exact_x=run.cfg.exact_x,
        )

    def _extra_steps(self, run: ADMMRun, problem, iters, steps: tuple) -> tuple:
        """Hook: subclasses append host-sampled per-step arrays (noise)."""
        return steps

    def _comm_per_iter(self, run: ADMMRun, problem) -> float:
        return 1.0

    # -- device side -------------------------------------------------------

    def setup(self, consts, statics):
        O, T, x_star, O_test, T_test, rho, mu = consts
        O, T = as_table_row(O), as_table_row(T)
        _, N, b, p = O.table.shape
        d = T.table.shape[3]
        dt = O.table.dtype
        MU = statics["MU"]
        rows = jnp.arange(MU)
        aux = dict(
            x_star=x_star,
            xs_norm=jnp.linalg.norm(x_star),
            # test error via the test set's Gram/cross matrices: p x p per
            # step instead of n_test x p (EXPERIMENTS.md §Perf).
            Gt=O_test.T @ O_test,
            Ct=O_test.T @ T_test,
            TTt=jnp.sum(T_test * T_test),
            n_test=O_test.shape[0],
            # O and T side by side as one pool of the table's rows
            # (`_pool`): each step fetches its K partitions' sub-batches
            # as K windows of MU consecutive rows, one start per window.
            pool=_pool(O, T, MU),
            rows=rows,
            valid=(rows < mu).astype(dt),
            inv_mu=1.0 / mu.astype(dt),
            part=jnp.arange(statics["K"], dtype=jnp.int32),
            rho=rho,
            b=b,
            shape=(N, p, d),
            dtype=dt,
            # Static tile for the fused Pallas x-update (lane-legal, no
            # gross padding of the flat (p*d,) parameter vector).
            block_n=fit_block_n(p * d),
        )
        if statics["exact_x"]:
            # I-ADMM exact solve operands: (O^T O / b + rho I), O^T T / b.
            O, T = O.table[O.row], T.table[T.row]
            aux["H"] = jnp.einsum("nbp,nbq->npq", O, O) / b
            aux["rhs0"] = jnp.einsum("nbp,nbd->npd", O, T) / b
            aux["eye"] = jnp.eye(p, dtype=dt)
        return aux

    def init(self, aux, statics):
        state = self.xyz_state(aux)
        if statics.get("ASYNC"):
            # Ring buffer of in-flight token increments (DESIGN.md §13):
            # slot s holds the sum of increments landing at the end of
            # the next iteration k with k % D == s.
            N, p, d = aux["shape"]
            state["pend"] = jnp.zeros((statics["D"], p, d), aux["dtype"])
        return state

    def step(self, state, inp, aux, statics):
        state, inp, aux = self._select_arm(state, inp, aux, statics)
        i, off, w, tk, gk = inp[0], inp[1], inp[2], inp[3], inp[4]
        x, y, z = state["x"], state["y"], state["z"]
        xi, yi = x[i], y[i]
        rho = aux["rho"]
        N = statics["N"]

        if statics["exact_x"]:
            x_new = jnp.linalg.solve(
                aux["H"][i] + rho * aux["eye"], aux["rhs0"][i] + rho * z + yi
            )
        else:
            # The K partitions' sub-batches, one window of MU rows each;
            # rows >= mu carry weight exactly 0 (they contribute exact
            # zeros to the gradient sums — batched == serial elementwise).
            Ob, Tb = _minibatch(aux, i, off, statics)
            # Per-ECN coded message: the masked sub-batch gradient g~_j
            # (eq. 6 before decode), one row of the fused kernel's msgs.
            r = (aux["valid"] * aux["inv_mu"])[None, :, None] * (Ob @ xi - Tb)
            msgs = jnp.einsum("kmp,kmd->kpd", Ob, r).reshape(
                statics["K"], -1
            )
            # Fused decode-combine + eq. (5a) through the Pallas hot path
            # (DESIGN.md §5); w already folds a^T B / K, so coeffs = w,
            # and inp[5] is the live-partition mask of this iteration's
            # alive set (exact-at-R or deadline-truncated, DESIGN.md §11).
            x_new = coded_admm_update(
                msgs, w, xi.ravel(), yi.ravel(), z.ravel(), tk, rho,
                inp[5], block_n=aux["block_n"],
            ).reshape(xi.shape)

        x_new = self._perturb_x(x_new, inp, aux, statics)
        if statics.get("ASYNC"):
            # Skipped activation (crashed agent / undecodable pattern):
            # act = 0 freezes x and y, making dz an exact zero below.
            # where-gating (not act-scaling) keeps the act = 1 path
            # bitwise identical to the ungated computation.
            act = inp[-1]
            x_new = jnp.where(act > 0, x_new, xi)
        y_new = yi + rho * gk * (z - x_new)  # eq. (5b)
        if statics.get("ASYNC"):
            y_new = jnp.where(act > 0, y_new, yi)
        dz = ((x_new - xi) - (y_new - yi) / rho) / N  # eq. (4c) increment
        state = dict(state, x=x.at[i].set(x_new), y=y.at[i].set(y_new))
        state = self._token_update(state, dz, inp, aux, statics)
        return state, self.metrics(state["x"], state["z"], aux)

    def _select_arm(self, state, inp, aux, statics):
        """Hook: the online controller resolves arm-stacked step inputs.

        Runs before anything else in :meth:`step`. The base family is
        non-adaptive — identity, so the synchronous/static paths keep
        their exact pre-controller trace. `repro.control.kernel`
        overrides this to pull a bandit arm from carry state, feed back
        the observed-response reward, and return a standard-layout
        pseudo-``inp`` selecting the live arm's schedule row
        (DESIGN.md §15).
        """
        return state, inp, aux

    def _perturb_x(self, x_new, inp, aux, statics):
        """Hook: pI-ADMM adds Gaussian noise to the shared primal."""
        return x_new

    def _token_increment(self, state, dz, inp, aux, statics):
        """Hook: compute the transmitted token increment.

        Returns ``(state_updates, c)`` where ``c`` is the increment the
        active agent actually ships (cq-sI-ADMM compresses dz here) and
        ``state_updates`` are carry entries the hook mutates (e.g. the
        error-feedback residual). Split from the z application so the
        async path can route ``c`` through the pend ring and gate the
        hook's state on the activity mask without knowing its keys.
        """
        return {}, dz

    def _token_update(self, state, dz, inp, aux, statics):
        """Apply the token increment: directly (sync) or via the pend
        ring with bounded staleness (async, DESIGN.md §13)."""
        upd, c = self._token_increment(state, dz, inp, aux, statics)
        if not statics.get("ASYNC"):
            return dict(state, **upd, z=state["z"] + c)
        wslot, rslot, act = inp[-3], inp[-2], inp[-1]
        # Dead activations transmit nothing and leave hook state alone.
        upd = {k: jnp.where(act > 0, v, state[k]) for k, v in upd.items()}
        pend = state["pend"].at[wslot].add(
            jnp.where(act > 0, c, jnp.zeros_like(c))
        )
        # Land every increment maturing at this iteration's boundary
        # (the read slot includes this step's own write when delta = 0 —
        # the synchronous landing).
        z = state["z"] + pend[rslot]
        pend = pend.at[rslot].set(jnp.zeros_like(state["z"]))
        return dict(state, **upd, z=z, pend=pend)

    def final(self, state, aux, statics):
        z = state["z"]
        if statics.get("ASYNC"):
            # Flush in-flight increments: the run ends, updates land.
            z = z + state["pend"].sum(axis=0)
        return state["x"], z


# Lanes of a TPU vector register: the pool's rows lie along them.
_LANES = 128


def _tiles(size: int) -> int:
    """Aligned lane tiles that hold any window of ``size`` rows."""
    return -(-size // _LANES) + 1


def _pool(O: TableRow, T: TableRow, pad: int) -> TableRow:
    """The (U, N, b, p) and (U, N, b, d) tables O and T as one pool of
    their N*b rows laid along lanes, (U, p + d, tiles, 128): row r of the
    pool is [:, :, r // 128, r % 128], and its rows from N*b on repeat
    the last row, as far as a window of ``pad`` rows can reach.

    A run with mu < MU reads up to MU - mu rows past its partition, and
    its last agent's last window up to MU - mu rows past the data. The
    repeated rows keep every window inside the pool, so no window start
    is clamped (which would shift the rows that carry weight), and a row
    past the data is the last row, as a clamped row index reads it.
    """
    U, N, b, _ = O.table.shape
    n = N * b
    tiles = (n + pad) // _LANES + _tiles(pad)

    def lanes(table):
        flat = table.reshape(U, n, -1).transpose(0, 2, 1)
        flat = jnp.pad(flat, ((0, 0), (0, 0), (0, tiles * _LANES - n)), mode="edge")
        return flat.reshape(U, -1, tiles, _LANES)

    pool = jnp.concatenate([lanes(O.table), lanes(T.table)], axis=1)
    return TableRow(pool, O.row)


def _windows(pool: TableRow, starts, size: int):
    """(len(starts), size, c): for each start s, the rows [s, s + size) of
    the run's row of ``pool`` (`_pool`), read from the table itself.

    Each window is fetched as the aligned lane tiles that hold it, whole
    (a gather of contiguous tiles, not of single rows), then moved to
    lane 0 by a barrel shifter: log2(128) fixed rotations, each taken or
    not by one bit of s % 128. Rows are only moved, never combined."""
    table, row = pool
    c, lanes = table.shape[1], table.shape[3]
    shape = (1, c, _tiles(size), lanes)
    row, zero = jnp.asarray(row, starts.dtype), jnp.zeros((), starts.dtype)

    def window(s):
        w = jax.lax.dynamic_slice(table, (row, zero, s // lanes, zero), shape)
        w = w.reshape(c, -1)
        for bit in range(lanes.bit_length() - 1):
            taken = ((s % lanes) >> bit) & 1 == 1
            w = jnp.where(taken, jnp.roll(w, -(1 << bit), axis=1), w)
        return w[:, :size].T

    return jax.vmap(window)(starts)


def _minibatch(aux, i, off, statics):
    """(Ob, Tb), (K, MU, p) and (K, MU, d): agent ``i``'s K partitions'
    sub-batches at offset ``off``, one window of MU rows each."""
    starts = i * aux["b"] + aux["part"] * statics["P"] + off
    rows = _windows(aux["pool"], starts, statics["MU"])
    p = aux["shape"][1]
    return rows[..., :p], rows[..., p:]


ADMM_KERNEL = register(IncrementalADMM(), "sI-ADMM", "csI-ADMM", "I-ADMM")
