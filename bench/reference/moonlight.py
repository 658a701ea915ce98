"""Plain Moonlight-16B-A3B (DeepSeek-V3 blocks) and three csI-ADMM steps
of the consensus trainer, on one chip's share of the experts.

The check of the cell ``moonlight-16b-a3b.consensus`` compares the
program's first three steps with these. The model follows the DeepSeek-V3
description (Hugging Face ``DeepseekV3ForCausalLM``, as Moonlight's
``config.json`` names it, ``model_type`` deepseek_v3): token embedding;
per layer a pre-norm attention block and a pre-norm feed-forward block;
a final RMSNorm and an untied output head.

- Attention (MLA, no q-LoRA): q = h W_q, per head 128 "nope" then 64
  "rope" dims; [c_kv, k_rope] = h W_kv_a; c_kv = RMSNorm(c_kv) (512);
  [k_nope, v] = c_kv W_kv_b per head (128 + 128). Rotary embedding goes on
  q_rope per head and on the one k_rope that every head shares, with
  dimensions (2i, 2i + 1) as a rotated pair at frequency theta^(-2i/64):
  the modeling code stores the rope dims interleaved and de-interleaves
  them before rotating halves. Keys and values are materialised per
  head; scores q.k over 192 dims scaled by 1/sqrt(192), causal softmax;
  the 16 x 128 outputs through W_o.
- Layer 0 is dense: SwiGLU of width 11,264.
- Layers 1 on: sigmoid scores s = sigmoid(h W_r) over all 64 experts; the
  top 6 of s + b chosen (b, the correction bias, only chooses); weights
  s_chosen / sum(s_chosen) x 2.446. This chip's share: each held expert
  (a SwiGLU of width 1,408) on every token, times its weight, which is 0
  where the expert was not chosen; the experts held elsewhere add
  nothing here. Two shared experts, one SwiGLU of width 2,816, added once.

It is written in straightforward ``jax.numpy`` in float32 at ``highest``
matmul precision. The layers run in a ``lax.scan`` recomputed in the
backward pass; attention and the experts run one row at a time and the
output head one row at a time (each recomputed in the backward pass), so
that it fits one chip beside the consensus state. Departures from the
published description: the norm scales are stored as offsets from 1
(the weight is ``1 + scale``); the weights are random from the seed
(``init``), not the trained ones; the correction bias is drawn from a
key of the configuration and not updated (no balancing rule runs), and
no auxiliary balance loss is used; the layers, the experts and the vocabulary are the chip's
share that the configuration's ``held`` names.

The steps are those of ``reference.qwen3`` (arXiv 2010.00914 Algorithm 2
in incremental mode), with its coded decode and its three-program
update. Planted faults for the check: ``capacity`` drops the
token-slots past capacity_factor x tokens x 6 / 64 on each held expert, in
token order, as a capacity-bound expert layer would; ``half_batch`` is
``reference.qwen3``'s. It imports nothing
of the program.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from reference import qwen3

HIGHEST = jax.lax.Precision.HIGHEST

__all__ = ["dims", "init", "loss", "leaf_norms", "run"]


def dims(config: dict) -> dict:
    """The published numbers of the configuration file, the share held on
    this chip (``held``) and the correction bias's key, as one flat dict
    of numbers."""
    m = {k: v for k, v in config.items() if isinstance(v, (int, float))
         and not isinstance(v, bool)}
    h = config["held"]
    m.update(layers_held=h["layers"], experts_held=h["experts"],
             expert_offset=h["expert_offset"], vocab_held=h["vocab"],
             router_bias_seed=config["correction_bias"]["seed"])
    return m


def shapes(m: dict) -> Dict[str, tuple]:
    """Leaf shapes of the trainer's parameter tree, each kind of layer
    stacked: ``dense_layers`` then ``layers`` (the expert layers)."""
    D, V = m["hidden_size"], m["vocab_held"]
    H, r = m["num_attention_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    Ld = m["first_k_dense_replace"]
    Le = m["layers_held"] - Ld
    E, Eh, Fe = m["n_routed_experts"], m["experts_held"], m["moe_intermediate_size"]
    Fs, F = m["n_shared_experts"] * Fe, m["intermediate_size"]
    out = {"embed": (V, D), "final_norm": (D,), "lm_head": (D, V)}
    for stack, L in (("dense_layers", Ld), ("layers", Le)):
        out.update({
            f"{stack}/ln1": (L, D), f"{stack}/ln2": (L, D),
            f"{stack}/wq": (L, D, H * (dn + dr)), f"{stack}/wkv_a": (L, D, r + dr),
            f"{stack}/kv_norm": (L, r), f"{stack}/wkv_b": (L, r, H * (dn + dv)),
            f"{stack}/wo": (L, H * dv, D),
        })
    out.update({
        "dense_layers/w_gate": (Ld, D, F), "dense_layers/w_up": (Ld, D, F),
        "dense_layers/w_down": (Ld, F, D),
        "layers/router": (Le, D, E), "layers/router_bias": (Le, E),
        "layers/w_gate": (Le, Eh, D, Fe), "layers/w_up": (Le, Eh, D, Fe),
        "layers/w_down": (Le, Eh, Fe, D),
        "layers/shared_gate": (Le, D, Fs), "layers/shared_up": (Le, D, Fs),
        "layers/shared_down": (Le, Fs, D),
    })
    return out


def _nest(flat: dict) -> dict:
    out: dict = {}
    for k, v in flat.items():
        if "/" in k:
            stack, leaf = k.split("/", 1)
            out.setdefault(stack, {})[leaf] = v
        else:
            out[k] = v
    return out


def flatten(params: dict) -> Dict[str, jax.Array]:
    out = {}
    for k, v in params.items():
        if isinstance(v, dict):
            out.update({f"{k}/{leaf}": a for leaf, a in v.items()})
        else:
            out[k] = v
    return out


def init(m: dict, seed: int, dtype: str) -> dict:
    """Random weights from the seed (its low 31 bits key the draw, the
    rest is folded in), made on the device in one call in ``dtype``:
    N(0, 0.02^2) matrices, each block's output projections (W_o and the
    experts' down projections) scaled by 1 / sqrt(layers held), norm
    scales 0. The correction bias is N(0, (0.005 sqrt(D))^2), the
    sigmoid scores' spread at these weights (a quarter of the router
    logits' std, 0.02 sqrt(D)), drawn from the configuration's own key
    ``router_bias_seed`` and not from the seed: at this scale the bias
    decides which experts take most tokens, so a bias drawn per seed
    would make the seed decide the load on this chip's experts."""
    return _init(qwen3.items(m), seed & 0x7FFFFFFF, seed >> 31, dtype)


@partial(jax.jit, static_argnums=(0, 3))
def _init(key_items, lo, hi, dtype):
    m = dict(key_items)
    key = jax.random.fold_in(jax.random.key(lo), hi)
    out_scale = 0.02 / m["layers_held"] ** 0.5
    flat = {}
    for i, (name, shape) in enumerate(sorted(shapes(m).items())):
        if "norm" in name or name.endswith(("ln1", "ln2")):
            flat[name] = jnp.zeros(shape, dtype)
            continue
        if name.endswith("router_bias"):
            w = jax.random.normal(jax.random.key(m["router_bias_seed"]), shape, jnp.float32)
            flat[name] = (w * 0.005 * m["hidden_size"] ** 0.5).astype(dtype)
            continue
        scale = out_scale if name.endswith(("wo", "w_down", "shared_down")) else 0.02
        w = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * scale
        flat[name] = w.astype(dtype)
    return _nest(flat)


# -- the model ---------------------------------------------------------------


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale)


def _rope(x, theta):
    """x (S, heads, d): dims (2i, 2i + 1) rotated as a pair by position
    times theta^(-2i/d); the result in halves order (first coordinates,
    then second), as the modeling code leaves it."""
    S, d = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv  # (S, d/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _swiglu(x, w_gate, w_up, w_down):
    return _mm(jax.nn.silu(_mm(x, w_gate)) * _mm(x, w_up), w_down)


def _attention(m, x, lp):
    """One row (S, D) through the MLA block: x + attention(x)."""
    S = x.shape[0]
    H, r = m["num_attention_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    a = _rmsnorm(x, lp["ln1"], eps)
    q = _mm(a, lp["wq"]).reshape(S, H, dn + dr)
    kv_a = _mm(a, lp["wkv_a"])
    c_kv = _rmsnorm(kv_a[:, :r], lp["kv_norm"], eps)
    k_rope = _rope(kv_a[:, None, r:], theta)  # (S, 1, dr), shared by every head
    kv = _mm(c_kv, lp["wkv_b"]).reshape(S, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rope, (S, H, dr))], axis=-1)
    v = kv[..., dn:]
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / (dn + dr) ** 0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST).reshape(S, H * dv)
    return x + _mm(o, lp["wo"])


def _routing(m, a, lp, capacity: Optional[float]):
    """(T, D) normed inputs -> (T, held experts) combine weights: the
    chosen experts' normalised scores x the scaling factor, 0 where an
    expert was not chosen; with ``capacity``, also 0 for the token-slots
    past capacity x T x 6 / 64 on their expert, in token order."""
    k, E = m["num_experts_per_tok"], m["n_routed_experts"]
    off, Eh = m["expert_offset"], m["experts_held"]
    s = jax.nn.sigmoid(_mm(a, lp["router"]))  # (T, E)
    _, idx = jax.lax.top_k(s + lp["router_bias"], k)
    chosen = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx].set(1.0)
    w = s * chosen
    w = w / jnp.sum(w, axis=-1, keepdims=True) * m["routed_scaling_factor"]
    w, chosen = w[:, off:off + Eh], chosen[:, off:off + Eh]
    if capacity is not None:
        C = max(1, int(capacity * s.shape[0] * k / E))
        before = jnp.cumsum(chosen, axis=0) - chosen
        w = w * (before < C)
    return w


def _experts(m, a, w, lp):
    """One row's share of the expert layer: each held expert on every
    token times its weight, and the shared experts once."""
    out = _swiglu(a, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    for e in range(m["experts_held"]):
        y = _swiglu(a, lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e])
        out = out + w[:, e:e + 1] * y
    return out


def _layer(m, h, lp, experts: bool, capacity: Optional[float]):
    """(B, S, D) through one layer, a row at a time."""
    B, S, D = h.shape
    eps = m["rms_norm_eps"]
    h = jax.lax.map(jax.checkpoint(lambda row: _attention(m, row, lp)), h)
    a = _rmsnorm(h, lp["ln2"], eps)
    if not experts:
        return h + jax.lax.map(jax.checkpoint(
            lambda row: _swiglu(row, lp["w_gate"], lp["w_up"], lp["w_down"])), a)
    w = _routing(m, a.reshape(B * S, D), lp, capacity).reshape(B, S, -1)
    return h + jax.lax.map(jax.checkpoint(lambda aw: _experts(m, *aw, lp)), (a, w))


def loss(m: dict, params: dict, tokens, labels, weights,
         capacity: Optional[float] = None) -> jax.Array:
    """sum_b weights_b * mean over positions of -log p(label | prefix)."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        h = p["embed"][tokens]
        for stack, experts in (("dense_layers", False), ("layers", True)):
            h, _ = jax.lax.scan(
                jax.checkpoint(lambda h, lp, e=experts: (_layer(m, h, lp, e, capacity), None)),
                h, p[stack],
            )
        h = _rmsnorm(h, p["final_norm"], m["rms_norm_eps"])
        nll = jax.lax.map(
            jax.checkpoint(lambda row: _row_nll(p["lm_head"], *row)), (h, labels))
        return jnp.sum(weights * nll)


def _row_nll(head, h, labels):
    """One row's mean over positions of -log p(label | prefix)."""
    logits = _mm(h, head)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


def leaf_norms(after: dict, before=None, scale: float = 1.0, index=None) -> Dict[str, float]:
    """scale * ||after - before|| per leaf in float32 (||after|| without
    ``before``); ``index`` picks one agent's slice of stacked leaves."""
    norms = qwen3._leaf_norms(flatten(after), None if before is None else flatten(before),
                              np.float32(scale), index)
    return {k: float(v) for k, v in norms.items()}


# -- three steps ---------------------------------------------------------------


@partial(jax.jit, static_argnums=(0, 5))
def _value_and_grad(key_items, params, tokens, labels, weights, capacity):
    m = dict(key_items)
    return jax.value_and_grad(lambda p: loss(m, p, tokens, labels, weights, capacity))(
        jax.tree.map(lambda a: a.astype(jnp.float32), params)
    )


@partial(jax.jit, static_argnums=(0, 5))
def _loss(key_items, params, tokens, labels, weights, capacity):
    return loss(dict(key_items), params, tokens, labels, weights, capacity)


def run(
    model: dict, cons: dict, seed: int, batches: List[dict], alive: List[np.ndarray],
    store: str, weights_dtype: str, capacity: Optional[float] = None,
    half_batch: bool = False,
) -> dict:
    """The losses of ``len(batches)`` steps, the first committing agent's
    gradient norm per leaf read back from its state after step 1, the
    change of z per leaf after the last step, and the norm of the float32
    gradient of step 1 per leaf (``reference.qwen3.run``'s numbers).

    ``store`` is the dtype the state is kept in; the weights are made in
    ``weights_dtype`` and then stored in ``store``. ``capacity`` drops
    the token-slots past that capacity factor on each held expert, and
    ``half_batch`` leaves out half of each agent's rows as
    ``reference.qwen3.run`` does (planted faults).
    """
    key_items = qwen3.items(model)
    A, rho = cons["n_agents"], cons["rho"]
    p0 = jax.tree.map(lambda a: a.astype(store), init(model, seed, weights_dtype))
    zeros = jax.tree.map(jnp.zeros_like, p0)
    x, y, z = [p0] * A, [zeros] * A, p0
    out = {"loss": []}
    for k, (batch, live) in enumerate(zip(batches, alive), start=1):
        rows = batch["tokens"].shape[0] // A
        w = qwen3.row_weights(cons, live, rows)
        if half_batch:
            w *= 2.0 * (qwen3.partitions(cons, rows) < cons["K"] // 2)
        tau = np.float32(cons["c_tau"] * np.sqrt(np.float32(k)))
        gamma = np.float32(cons["c_gamma"] / np.sqrt(np.float32(k)))
        act = (k - 1) % A
        losses = []
        for a in range(A):
            sl = slice(a * rows, (a + 1) * rows)
            args = (jnp.asarray(batch["tokens"][sl]), jnp.asarray(batch["labels"][sl]),
                    jnp.asarray(w[a]), capacity)
            if a == act:
                val, g = _value_and_grad(key_items, x[a], *args)
            else:
                val = _loss(key_items, x[a], *args)
            losses.append(float(val))
        out["loss"].append(float(np.mean(losses)))
        if k == 1:
            out["ref_grad"] = leaf_norms(g)
        x_old = x[act]
        x[act], y[act], z = qwen3._update(x[act], y[act], z, g, rho, A, tau, gamma)
        del g
        if k == 1:
            out["grad_norm"] = leaf_norms(x_old, x[act], float(rho + tau))
        del x_old
    p0 = jax.tree.map(lambda a: a.astype(store), init(model, seed, weights_dtype))
    out["z_change"] = leaf_norms(z, p0)
    return out
