"""Consensus-training traffic: steps of the csI-ADMM trainer, one after
another, on one model.

A traffic file of this kind gives the rows per step, the consensus
settings (agents, ECNs per agent, stragglers tolerated, code, rho, c_tau,
c_gamma, mode), the synthetic token stream and the steps that the check
compares. The sequence length, the dtype and the activation
checkpointing are the configuration's. Each step's batch is built on the
host the way the trainer's launcher builds it: each agent draws K
partitions of P rows from its own token stream (an order-1 Markov chain
over the vocabulary with a share of uniform noise tokens, seeded by the
run's seed and the agent), and ECN j's rows hold the partitions it
stores, so that rows are laid out (agent, ECN, stored partition, P).
One random ECN per agent and tolerated straggler is dead in each step.
The same sizes for every seed; the seed draws the weights, the tokens
and the stragglers.

The program is entered at ``ConsensusRuntime.train_step``, jitted with
the state donated. The benchmark makes the weights from the seed
(``reference.qwen3.init``) and lays out the consensus state (x and y per
agent, z, the step count) as the trainer does. Set-up drives that one
state through the first ``check.steps`` steps, reading the losses, the
first committing agent's gradient as its state after step 1 gives it
back, G = (rho + tau_1) (x_0 - x_1), and the change of z; the window
goes on from there with the same step function and feed. Losses are read
back once the window has closed. The check frees the program's state and
runs the same steps with the plain reference (``reference.qwen3``).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import work
from reference import qwen3


class TokenStream:
    """One agent's synthetic tokens: each token is one of ``branching``
    successors of the last, drawn uniformly, or with probability
    ``noise`` a uniform token."""

    def __init__(self, vocab: int, seed: list, branching: int, noise: float):
        rng = np.random.default_rng(seed)
        self.succ = rng.integers(0, vocab, size=(vocab, branching))
        self.rng = np.random.default_rng(seed + [1])
        self.vocab, self.branching, self.noise = vocab, branching, noise
        self.state = int(self.rng.integers(0, vocab))

    def sample(self, n: int) -> np.ndarray:
        noisy = self.rng.random(n) < self.noise
        choice = self.rng.integers(0, self.branching, size=n)
        noise_tok = self.rng.integers(0, self.vocab, size=n)
        out = np.empty(n, np.int32)
        s = self.state
        for t in range(n):
            s = int(noise_tok[t]) if noisy[t] else int(self.succ[s, choice[t]])
            out[t] = s
        self.state = s
        return out

    def rows(self, n: int, seq: int) -> Dict[str, np.ndarray]:
        """Next-token rows: labels are the tokens shifted left by one."""
        raw = self.sample(n * (seq + 1)).reshape(n, seq + 1)
        return {"tokens": raw[:, :-1], "labels": raw[:, 1:]}


def model_config(config: dict):
    """The program's model configuration, from the configuration file."""
    from repro.models import ModelConfig

    m = config["model"]
    return ModelConfig(
        name=config["name"], family="dense", n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], vocab=m["vocab_size"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        head_dim=m["head_dim"], qk_norm=True, rope_theta=m["rope_theta"],
        d_ff=m["intermediate_size"], mlp_act="swiglu",
        tie_embeddings=m["tie_word_embeddings"], dtype=config["dtype"],
        remat=config["remat"],
    )


@partial(jax.jit, static_argnums=(0, 3, 4))
def _initial_state(items, lo, hi, dtype, A):
    """The trainer's initial consensus state from the seed's weights:
    x_a = z = the weights, y_a = 0, k = 0 (one call on the device)."""
    z = qwen3._init(items, lo, hi, dtype)
    return {
        "x": jax.tree.map(lambda p: jnp.broadcast_to(p, (A, *p.shape)), z),
        "y": jax.tree.map(lambda p: jnp.zeros((A, *p.shape), p.dtype), z),
        "z": z,
        "k": jnp.zeros((), jnp.int32),
    }


class Workload:
    def __init__(self, config: dict, traffic: dict, seed: int):
        # The program under test, entered here and nowhere else.
        from repro.distributed import ConsensusConfig, ConsensusRuntime
        from repro.models import get_model

        self.config, self.traffic, self.seed = config, traffic, seed
        self.model, self.cons = config["model"], traffic["consensus"]
        c = self.cons
        runtime = ConsensusRuntime(
            get_model(model_config(config)),
            ConsensusConfig(
                n_agents=c["n_agents"], K=c["K"], S=c["S"], scheme=c["scheme"],
                rho=c["rho"], c_tau=c["c_tau"], c_gamma=c["c_gamma"], mode=c["mode"],
                seed=c["code_seed"],
            ),
            jax.make_mesh((1, 1, 1), ("agent", "data", "model")),
        )
        self._train_step = jax.jit(runtime.train_step, donate_argnums=0)
        A, K, S = c["n_agents"], c["K"], c["S"]
        self.rows_per_agent = traffic["rows_per_step"] // A
        self.P = self.rows_per_agent // (K * (S + 1))
        if self.P * K * (S + 1) * A != traffic["rows_per_step"]:
            raise ValueError("rows_per_step must be agents x ECNs x (S + 1) x P")
        tok = traffic["tokens"]
        self.streams = [
            TokenStream(self.model["vocab_size"], [seed, a], tok["branching"], tok["noise"])
            for a in range(A)
        ]
        self.stragglers = np.random.default_rng([seed, 7])
        self.support = qwen3.support(c)
        self.state = None

    # -- the feed and the step ---------------------------------------------

    def batch(self):
        """One step's rows in coded allocation, and the (A, K) alive mask."""
        c, seq = self.cons, self.config["seq_len"]
        rows = []
        for stream in self.streams:
            parts = [stream.rows(self.P, seq) for _ in range(c["K"])]
            rows += [parts[t] for j in range(c["K"]) for t in self.support[j]]
        batch = {k: np.concatenate([r[k] for r in rows]) for k in rows[0]}
        alive = np.ones((c["n_agents"], c["K"]), bool)
        for a in range(c["n_agents"]):
            alive[a, self.stragglers.choice(c["K"], size=c["S"], replace=False)] = False
        return batch, alive

    def _run_step(self):
        batch, alive = self.batch()
        feed = {k: jnp.asarray(v) for k, v in batch.items()}
        self.state, metrics = self._train_step(self.state, feed, jnp.asarray(alive))
        return batch, alive, metrics

    def _weights(self):
        return qwen3.init(self.model, self.seed, self.config["dtype"])

    def warm_up(self) -> None:
        """The first ``check.steps`` steps, read for the check."""
        self.state = _initial_state(
            qwen3.items(self.model), self.seed & 0x7FFFFFFF, self.seed >> 31, self.config["dtype"],
            self.cons["n_agents"],
        )
        self.fed, losses = [], []
        for k in range(1, self.traffic["check"]["steps"] + 1):
            batch, alive, metrics = self._run_step()
            self.fed.append((batch, alive))
            losses.append(metrics["loss"])
            if k == 1:
                tau = np.float32(self.cons["c_tau"])
                self.grad_norm = qwen3.leaf_norms(
                    self.state["x"], self._weights(), float(self.cons["rho"] + tau), index=0)
        self.z_change = qwen3.leaf_norms(self.state["z"], self._weights())
        self.losses = [float(v) for v in losses]

    def step(self, i: int) -> dict:
        """One step, dispatched; its loss stays on the device."""
        return {"loss": self._run_step()[2]["loss"]}

    def finish(self) -> None:
        """Waits until the last step's state is on the device."""
        jax.block_until_ready(self.state)

    def counters(self, records: List[dict]) -> dict:
        """Steps, the committing agent's tokens, and their training
        operations (``work.train_flops_per_token``)."""
        m, seq = self.model, self.config["seq_len"]
        tokens = len(records) * self.rows_per_agent * seq
        per_token = work.train_flops_per_token(
            m["num_hidden_layers"], m["hidden_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["head_dim"], m["intermediate_size"],
            m["vocab_size"], seq,
        )
        return {"steps": len(records), "tokens": tokens, "flops": tokens * per_token}

    # -- correctness -------------------------------------------------------

    def failed(self, records: List[dict]) -> int:
        """Steps whose loss is not finite."""
        losses = np.asarray(jax.device_get([r["loss"] for r in records]), np.float64)
        return int((~np.isfinite(losses)).sum())

    def _reference(self, fed, store: str, half_batch: bool = False) -> dict:
        return qwen3.run(
            self.model, self.cons, self.seed, [b for b, _ in fed], [a for _, a in fed],
            store=store, weights_dtype=self.config["dtype"], half_batch=half_batch,
        )

    def _gaps(self, got: dict, ref: dict) -> Dict[str, dict]:
        """Each number beside its limit: the largest relative gap of the
        steps' losses, and of the gradient's and z's change norms by the
        worst leaf, each against the reference's norm of that leaf or of
        the median leaf, whichever is larger. Leaves whose step-1
        reference gradient is under a thousandth of the median leaf's are
        left out (their change is round-off)."""
        med = float(np.median(list(ref["ref_grad"].values())))
        leaves = [k for k, v in ref["ref_grad"].items() if v >= 1e-3 * med]
        out = {"loss": max(abs(a - b) / abs(b) for a, b in zip(got["loss"], ref["loss"]))}
        for name in ("grad_norm", "z_change"):
            floor = float(np.median([ref[name][k] for k in leaves]))
            out[name] = max(
                abs(got[name][k] - ref[name][k]) / max(ref[name][k], floor) for k in leaves
            )
        limits = self.traffic["check"]["limits"]
        return {k: {"value": out[k], "limit": limits[k]} for k in limits}

    def check(self, records: List[dict]) -> Dict[str, dict]:
        """The set-up's first steps against the reference; the program's
        state is freed first."""
        self.state = None
        got = {"loss": self.losses, "grad_norm": self.grad_norm, "z_change": self.z_change}
        return self._gaps(got, self._reference(self.fed, self.config["dtype"]))

    def _fresh_feed(self) -> list:
        return [self.batch() for _ in range(self.traffic["check"]["steps"])]

    def control(self, dtype: str) -> Dict[str, dict]:
        """The reference with its state kept in ``dtype`` put in the
        program's place, on the feed the first steps would get."""
        fed = self._fresh_feed()
        return self._gaps(self._reference(fed, dtype), self._reference(fed, self.config["dtype"]))

    def fault(self, name: str) -> Dict[str, dict]:
        """The reference with a planted fault in the program's place:
        ``half_batch`` leaves out half of each agent's rows and takes the
        mean over the rest."""
        if name != "half_batch":
            raise ValueError(f"unknown fault {name!r}")
        fed = self._fresh_feed()
        return self._gaps(self._reference(fed, self.config["dtype"], half_batch=True),
                          self._reference(fed, self.config["dtype"]))
