"""Consensus-training traffic on a model with latent attention and
DeepSeek-V3 expert layers (``moonlight-16b-a3b``): the ``consensus``
generator's steps, feed and check, with

- the program's model configuration built from the configuration file's
  published keys and the share of layers, experts and vocabulary that it
  says this chip holds (``held``);
- the weights and the plain reference of ``reference.moonlight``; its
  planted fault ``dropped_tokens`` drops the token-slots past a capacity
  of 1.0 on each held expert (``half_batch`` is kept);
- the counters: each step's routing counters (``moe/*`` of
  ``ConsensusRuntime.train_step``) stay on the device and are summed
  when the window has closed; the training operations count the routed
  experts' work from the committing agent's held token-slots
  (``bench/work_moe.py``), and the grouped expert products' least
  operations and bytes from every agent's.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import work_moe
from generators import consensus
from reference import moonlight, qwen3

CAPACITY = 1.0  # the dropped-token fault's capacity factor


def model_config(config: dict):
    """The program's model configuration, from the configuration file."""
    from repro.models import ModelConfig

    m = moonlight.dims(config)
    return ModelConfig(
        name=config["name"], family="moe", n_layers=m["layers_held"],
        d_model=m["hidden_size"], vocab=m["vocab_held"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        kv_lora_rank=m["kv_lora_rank"], qk_nope_head_dim=m["qk_nope_head_dim"],
        qk_rope_head_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        rope_theta=float(m["rope_theta"]), norm_eps=m["rms_norm_eps"],
        d_ff=m["intermediate_size"], first_dense_layers=m["first_k_dense_replace"],
        n_experts=m["n_routed_experts"], experts_per_token=m["num_experts_per_tok"],
        d_expert=m["moe_intermediate_size"], n_shared_experts=m["n_shared_experts"],
        routed_scale=m["routed_scaling_factor"], experts_held=m["experts_held"],
        expert_offset=m["expert_offset"], dtype=config["dtype"], remat=config["remat"],
        moe_impl="pallas",
    )


@partial(jax.jit, static_argnums=(0, 3, 4))
def _initial_state(items, lo, hi, dtype, A):
    """x_a = z = the weights, y_a = 0, k = 0 (one call on the device)."""
    z = moonlight._init(items, lo, hi, dtype)
    return {
        "x": jax.tree.map(lambda p: jnp.broadcast_to(p, (A, *p.shape)), z),
        "y": jax.tree.map(lambda p: jnp.zeros((A, *p.shape), p.dtype), z),
        "z": z,
        "k": jnp.zeros((), jnp.int32),
    }


class Workload(consensus.Workload):
    def __init__(self, config: dict, traffic: dict, seed: int):
        # The program under test, entered here and nowhere else.
        from repro.distributed import ConsensusConfig, ConsensusRuntime
        from repro.models import get_model

        self.config, self.traffic, self.seed = config, traffic, seed
        self.model, self.cons = moonlight.dims(config), traffic["consensus"]
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
            consensus.TokenStream(self.model["vocab_held"], [seed, a], tok["branching"],
                                  tok["noise"])
            for a in range(A)
        ]
        self.stragglers = np.random.default_rng([seed, 7])
        self.support = qwen3.support(c)
        self.state = None

    def _weights(self):
        return moonlight.init(self.model, self.seed, self.config["dtype"])

    def warm_up(self) -> None:
        """The first ``check.steps`` steps, read for the check."""
        self.state = _initial_state(
            qwen3.items(self.model), self.seed & 0x7FFFFFFF, self.seed >> 31,
            self.config["dtype"], self.cons["n_agents"],
        )
        self.fed, losses = [], []
        for k in range(1, self.traffic["check"]["steps"] + 1):
            batch, alive, metrics = self._run_step()
            self.fed.append((batch, alive))
            losses.append(metrics["loss"])
            if k == 1:
                tau = np.float32(self.cons["c_tau"])
                self.grad_norm = moonlight.leaf_norms(
                    self.state["x"], self._weights(), float(self.cons["rho"] + tau), index=0)
        self.z_change = moonlight.leaf_norms(self.state["z"], self._weights())
        self.losses = [float(v) for v in losses]

    def step(self, i: int) -> dict:
        """One step, dispatched; its loss and routing counters stay on
        the device."""
        metrics = self._run_step()[2]
        return {k: v for k, v in metrics.items() if k == "loss" or k.startswith("moe/")}

    def counters(self, records: List[dict]) -> dict:
        """Steps, the committing agent's tokens and their training
        operations (``work_moe``), the routing counters summed over the
        steps (``moe/max_rows``: the most on one held expert in any
        layer, agent and step), and the grouped expert products' least
        operations and bytes (``expert_gmm``)."""
        m, seq = self.model, self.config["seq_len"]
        moe = jax.device_get([{k: v for k, v in r.items() if k != "loss"} for r in records])
        total = {k: int(sum(int(r[k]) for r in moe)) for k in moe[0]} if moe else {}
        if moe:
            total["moe/max_rows"] = max(int(r["moe/max_rows"]) for r in moe)
        tokens = len(records) * self.rows_per_agent * seq
        committed = total.get("moe/committed_rows", 0)
        flops = tokens * work_moe.train_flops_per_token(m, seq) + work_moe.routed_flops(m, committed)
        passes = len(records) * self.cons["n_agents"] * (m["layers_held"] - m["first_k_dense_replace"])
        gmm_flops, gmm_bytes = work_moe.expert_gmm_step(
            m, total.get("moe/held_rows", 0), passes,
            forward_runs=2 if self.config["remat"] == "full" else 1)
        return dict(total, steps=len(records), tokens=tokens, flops=flops,
                    expert_gmm={"flops": gmm_flops, "bytes": gmm_bytes})

    def _reference(self, fed, store: str, half_batch: bool = False, capacity=None) -> dict:
        return moonlight.run(
            self.model, self.cons, self.seed, [b for b, _ in fed], [a for _, a in fed],
            store=store, weights_dtype=self.config["dtype"], capacity=capacity,
            half_batch=half_batch,
        )

    def fault(self, name: str) -> Dict[str, dict]:
        """The reference with a planted fault in the program's place:
        ``dropped_tokens`` drops the token-slots past a capacity of 1.0
        on each held expert; ``half_batch`` is the ``consensus``
        generator's."""
        if name != "dropped_tokens":
            return super().fault(name)
        fed = self._fresh_feed()
        return self._gaps(self._reference(fed, self.config["dtype"], capacity=CAPACITY),
                          self._reference(fed, self.config["dtype"]))
