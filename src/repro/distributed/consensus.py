"""csI-ADMM as a distributed-training feature on a TPU mesh.

Mapping (DESIGN.md §3):

  agents  -> the mesh's "agent" axis (the pod axis on multi-pod meshes, a
             data-axis split on single-pod meshes). Agent i's primal/dual
             (x_i, y_i) are pytrees with a leading A dim sharded over
             "agent" — each agent's copy lives only on its subgroup, so
             per-device bytes match ONE FSDP-sharded model, not A of them.
  z token -> consensus variable sharded over ("agent","data") — the paper's
             token traversal becomes an all-gather of z over the agent axis
             (one model's worth of ICI traffic per step, the exact analogue
             of "one token hop per iteration").
  ECNs    -> K equal subgroups of each agent's data axis. The input batch
             arrives CODED-ALLOCATED (dataloader repeats partition t on the
             S+1 ECNs whose encode rows touch it, paper Alg. 2 steps 2-9),
             so rows are laid out (A, K, S+1, P) along dim 0.

The encode/decode collapses into one weighted backward pass: gradients are
linear in per-example losses, so ECN j's encoded message sum_t B[j,t] g~_t
followed by the agent's decode sum_j a_j g_j is the gradient of the
row-weighted loss with w_row = a_j * B[j, t(row)] / (K * P). The decode
vector a(alive) is recomputed in-jit from the straggler mask via pinv —
dead ECNs get coefficient exactly 0 (min-norm solution), so their rows'
compute is masked out just like a timed-out response.

Redundancy is honest: the assigned global batch B carries (S+1)-replicated
rows, so the effective mini-batch is B/(S+1) — eq. (22)'s M_bar = M/(S+1)
trade-off, visible in the framework rather than assumed.

Modes:
  incremental (paper-faithful): only agent (k mod A) applies its update;
      all agents compute (SPMD lockstep) but non-active deltas are masked.
  parallel (beyond-paper): every agent updates every step (PW-ADMM-style);
      z absorbs the average delta. Same per-step cost, A x the progress —
      recorded separately in EXPERIMENTS.md §Perf.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.coding import GradientCode, make_code

from .sharding import AxisLayout, tree_specs

__all__ = ["ConsensusConfig", "ConsensusRuntime"]


@dataclasses.dataclass(frozen=True)
class ConsensusConfig:
    """Hyper-parameters of the distributed csI-ADMM runtime."""

    n_agents: int = 2
    K: int = 4  # ECN groups per agent
    S: int = 1  # tolerated stragglers per agent
    scheme: str = "cyclic"  # "uncoded" | "fractional" | "cyclic"
    rho: float = 1.0
    c_tau: float = 0.1  # tau^k = c_tau sqrt(k)
    c_gamma: float = 1.0  # gamma^k = c_gamma / sqrt(k)
    mode: str = "incremental"  # "incremental" (paper) | "parallel" (beyond)
    seed: int = 0

    def code(self) -> GradientCode:
        return make_code(self.scheme, self.K, self.S, seed=self.seed)


def make_consensus_mesh(
    n_agents: int, multi_pod: bool = False
) -> Mesh:
    """The production mesh refined with an explicit agent axis.

    multi-pod: the pod axis IS the agent axis ((2,16,16) ->
    ("agent","data","model"), 512 chips). single-pod: the 16-wide data axis
    splits into (agents, data) ((A, 16//A, 16), 256 chips).
    """
    if multi_pod:
        if n_agents != 2:
            raise ValueError("multi-pod mesh has 2 pods = 2 agents")
        return jax.make_mesh((2, 16, 16), ("agent", "data", "model"))
    if 16 % n_agents:
        raise ValueError(f"n_agents={n_agents} must divide 16")
    return jax.make_mesh(
        (n_agents, 16 // n_agents, 16), ("agent", "data", "model")
    )


class ConsensusRuntime:
    """Builds sharded init / train-step callables for one (model, mesh)."""

    def __init__(self, model, cfg: ConsensusConfig, mesh: Mesh):
        self.model = model
        self.cfg = cfg
        self.mesh = mesh
        self.layout = AxisLayout(mesh, data=("data",), model="model", agent="agent")
        code = cfg.code()
        # Static encode-structure constants: ECN j's u-th stored partition
        # id and its encode coefficient B[j, supp(j)[u]].
        sup = np.stack([code.support(j) for j in range(cfg.K)])  # (K, S+1)
        if sup.shape[1] != cfg.S + 1:
            raise ValueError(
                f"{cfg.scheme} code stores {sup.shape[1]} partitions/ECN, "
                f"expected S+1={cfg.S + 1}"
            )
        self.B_enc = jnp.asarray(code.B, jnp.float32)  # (K, K)
        self.B_sel = jnp.asarray(
            np.take_along_axis(code.B, sup, axis=1), jnp.float32
        )  # (K, S+1)
        self.support = jnp.asarray(sup, jnp.int32)

    # -- state ---------------------------------------------------------------

    def state_shape(self, params_shape: Any) -> Any:
        """Abstract consensus state from abstract params (dry-run safe)."""
        A = self.cfg.n_agents

        def rep(leaf):
            return jax.ShapeDtypeStruct((A, *leaf.shape), leaf.dtype)

        return {
            "x": jax.tree.map(rep, params_shape),
            "y": jax.tree.map(rep, params_shape),
            "z": params_shape,
            "k": jax.ShapeDtypeStruct((), jnp.int32),
        }

    def state_specs(self, params_shape: Any) -> Any:
        ly = self.layout
        zly = AxisLayout(self.mesh, data=("agent", "data"), model="model")
        return {
            "x": tree_specs(
                jax.tree.map(
                    lambda l: jax.ShapeDtypeStruct((self.cfg.n_agents, *l.shape), l.dtype),
                    params_shape,
                ),
                ly,
                leading=("agent",),
            ),
            "y": tree_specs(
                jax.tree.map(
                    lambda l: jax.ShapeDtypeStruct((self.cfg.n_agents, *l.shape), l.dtype),
                    params_shape,
                ),
                ly,
                leading=("agent",),
            ),
            # z FSDP-shards over BOTH agent and data axes: the per-step
            # all-gather of z over "agent" is the token traversal.
            "z": tree_specs(params_shape, zly),
            "k": P(),
        }

    def init_state(self, rng: jax.Array) -> Any:
        """Concrete init (small models / examples; z = init params, x=y=0)."""
        params = self.model.init(rng)
        A = self.cfg.n_agents
        x = jax.tree.map(lambda p: jnp.broadcast_to(p, (A, *p.shape)).copy(), params)
        y = jax.tree.map(lambda p: jnp.zeros((A, *p.shape), p.dtype), params)
        return {"x": x, "y": y, "z": params, "k": jnp.zeros((), jnp.int32)}

    # -- step ----------------------------------------------------------------

    def row_weights(self, alive: jax.Array, rows_per_agent: int) -> jax.Array:
        """(A, rows_per_agent) loss weights from the (A, K) alive mask.

        Decode vector per agent: min-norm a with a^T (B masked to alive rows)
        = 1^T; dead ECNs receive coefficient exactly 0 (their e_j lies in
        null(B_alive^T), and the pinv solution is orthogonal to it).
        """
        cfg = self.cfg
        K, S1 = cfg.K, cfg.S + 1
        P_rows = rows_per_agent // (K * S1)
        # Solve in the widest enabled precision (f64 under x64, else f32) —
        # decode exactness is a property of the certified code; the solve
        # should not be the noise floor.
        ftype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
        Bm = self.B_enc.astype(ftype)[None] * alive[..., None].astype(ftype)
        ones = jnp.ones((cfg.K,), ftype)
        a = jax.vmap(lambda M: jnp.linalg.pinv(M.T, rtol=1e-6) @ ones)(Bm)
        a = a.astype(jnp.float32)
        # w[a, j, u, :] = a_j * B[j, sup(j)[u]] / (K * P)
        w = (
            a[:, :, None] * self.B_sel[None] / (K * P_rows)
        )  # (A, K, S+1)
        return jnp.broadcast_to(
            w[..., None], (*w.shape, P_rows)
        ).reshape(alive.shape[0], rows_per_agent)

    def train_step(
        self, state: Any, batch: Any, alive: jax.Array
    ) -> Tuple[Any, dict]:
        """One csI-ADMM iteration (eqs. 5a, 5b, 4c) over the mesh.

        batch leaves are (B_global, ...) with B_global = A*K*(S+1)*P rows in
        coded allocation order; alive is the (A, K) ECN response mask.
        """
        cfg = self.cfg
        A = cfg.n_agents
        k = state["k"] + 1
        kf = k.astype(jnp.float32)
        tau = cfg.c_tau * jnp.sqrt(kf)
        gamma = cfg.c_gamma / jnp.sqrt(kf)
        rho = cfg.rho

        tokens = batch["tokens"]
        Bg = tokens.shape[0]
        rows = Bg // A
        w = self.row_weights(alive, rows)  # (A, rows)

        def reshape_agent(leaf):
            return leaf.reshape(A, rows, *leaf.shape[1:])

        abatch = jax.tree.map(reshape_agent, batch)

        def agent_loss(x_a, batch_a, w_a):
            b = dict(batch_a, loss_weights=w_a)
            (loss, metrics), grads = jax.value_and_grad(
                self.model.loss, has_aux=True
            )(x_a, b)
            counters = {k: v for k, v in metrics.items() if k.startswith("moe/")}
            return grads, loss, metrics["nll"], counters

        grads, losses, nlls, counters = jax.vmap(agent_loss)(
            state["x"], abatch, w
        )  # grads: (A, ...) pytree; counters: (A,) each

        # eq. (5a): x+ = (tau x + rho z + y - G) / (rho + tau), all agents.
        def x_upd(x, y, z, g):
            num = (
                tau * x.astype(jnp.float32)
                + rho * z[None].astype(jnp.float32)
                + y.astype(jnp.float32)
                - g.astype(jnp.float32)
            )
            return (num / (rho + tau)).astype(x.dtype)

        x_new = jax.tree.map(x_upd, state["x"], state["y"], state["z"], grads)

        # eq. (5b): y+ = y + rho gamma (z - x+).
        def y_upd(y, z, xn):
            return (
                y.astype(jnp.float32)
                + rho * gamma * (z[None].astype(jnp.float32) - xn.astype(jnp.float32))
            ).astype(y.dtype)

        y_new = jax.tree.map(y_upd, state["y"], state["z"], x_new)

        if cfg.mode == "incremental":
            # Paper-faithful: only agent i_k = (k-1) mod A commits.
            active = (k - 1) % A
            m = (jnp.arange(A) == active).astype(jnp.float32)

            def sel(new, old):
                mm = m.reshape((A,) + (1,) * (new.ndim - 1)).astype(jnp.float32)
                return (
                    mm * new.astype(jnp.float32)
                    + (1 - mm) * old.astype(jnp.float32)
                ).astype(new.dtype)

            x_new = jax.tree.map(sel, x_new, state["x"])
            y_new = jax.tree.map(sel, y_new, state["y"])
            scale = 1.0 / A  # eq. (4c) 1/N with one active delta
            mask = m
        else:  # parallel (beyond-paper): every agent commits, z averages
            scale = 1.0 / A
            mask = jnp.ones((A,), jnp.float32)

        # eq. (4c): z+ = z + sum_a mask_a [(x_a+ - x_a) - (y_a+ - y_a)/rho]/A.
        def z_upd(z, xn, xo, yn, yo):
            mm = mask.reshape((A,) + (1,) * (xn.ndim - 1))
            delta = (
                (xn.astype(jnp.float32) - xo.astype(jnp.float32))
                - (yn.astype(jnp.float32) - yo.astype(jnp.float32)) / rho
            )
            return (
                z.astype(jnp.float32) + scale * jnp.sum(mm * delta, axis=0)
            ).astype(z.dtype)

        z_new = jax.tree.map(
            z_upd, state["z"], x_new, state["x"], y_new, state["y"]
        )

        # consensus residual ||z - x_a|| (flattened, f32)
        def sq(xn, z):
            d = xn.astype(jnp.float32) - z[None].astype(jnp.float32)
            return jnp.sum(d * d, axis=tuple(range(1, d.ndim)))

        res = jnp.sqrt(
            sum(jax.tree.leaves(jax.tree.map(sq, x_new, z_new)))
        )  # (A,)

        new_state = {"x": x_new, "y": y_new, "z": z_new, "k": k}
        metrics = {
            "loss": losses.mean(),
            "nll": nlls.mean(),
            "consensus_residual": res.mean(),
            "tau": tau,
            "gamma": gamma,
        }
        if counters:
            # An expert layer's routing: over layers and agents, and the
            # slots of the committing agents alone (their work counts).
            metrics.update(
                {k: v.max() if k == "moe/max_rows" else v.sum() for k, v in counters.items()}
            )
            metrics["moe/committed_rows"] = jnp.sum(
                mask.astype(jnp.int32) * counters["moe/held_rows"])
        return new_state, metrics

    # -- jit plumbing ----------------------------------------------------------

    def lower_train_step(self, batch_shape: Any, params_shape: Any):
        """jit-lower the step on the mesh with explicit shardings (dry-run)."""
        state_shape = self.state_shape(params_shape)
        specs = self.state_specs(params_shape)
        from .sharding import batch_specs

        bspecs = batch_specs(batch_shape, self.layout)
        alive_shape = jax.ShapeDtypeStruct(
            (self.cfg.n_agents, self.cfg.K), jnp.bool_
        )
        with self.mesh:
            step = jax.jit(
                self.train_step,
                in_shardings=(
                    jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs),
                    jax.tree.map(lambda s: NamedSharding(self.mesh, s), bspecs),
                    NamedSharding(self.mesh, P()),
                ),
                out_shardings=(
                    jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs),
                    None,
                ),
            )
            return step.lower(state_shape, batch_shape, alive_shape)
