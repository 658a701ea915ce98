"""Plain Qwen3 and three csI-ADMM steps of the consensus trainer.

The check of the training cell compares the program's first three steps
with these. The model follows the Qwen3 description (Hugging Face
``Qwen3ForCausalLM``): token embedding, per layer a pre-norm attention
block (RMSNorm, q/k/v projections, RMSNorm of each query and key head,
rotary embedding on the two halves of each head, causal softmax
attention with each key/value head shared by ``n_heads / n_kv_heads``
query heads, output projection) and a pre-norm SwiGLU MLP, a final
RMSNorm and the tied embedding as the output head. It is written in
straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision; the layers run in a ``lax.scan`` with each layer
recomputed in the backward pass, and the output head one row at a time
(its logits recomputed in the backward pass), so that it fits one chip
beside the consensus state. Departures from the published description:
the norm scales are stored as offsets from 1 (the weight is
``1 + scale``), and the weights are random from the seed (``init``), not
the trained ones.

The steps follow arXiv 2010.00914 Algorithm 2 as the trainer runs it in
incremental mode: at step k (from 1) agent ``(k - 1) mod A`` commits,
with tau = c_tau sqrt(k) and gamma = c_gamma / sqrt(k),

- G: the gradient of the agent's row-weighted loss, sum_b w_b * (mean
  token NLL of row b), where w_b = a_j B[j, t] / (K P) for the row of
  partition t on ECN j and a solves a^T B[alive] = 1^T (least squares,
  float64, zero on dead ECNs);
- eq. (5a) x+ = (tau x + rho z + y - G) / (rho + tau);
- eq. (5b) y+ = y + rho gamma (z - x+);
- eq. (4c) z+ = z + ((x+ - x) - (y+ - y) / rho) / A,

each computed in float32 from the stored values and stored in the
configuration's dtype (``store``). It imports nothing of the program.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from reference.lsq_admm import make_code

HIGHEST = jax.lax.Precision.HIGHEST

__all__ = ["init", "loss", "support", "row_weights", "leaf_norms", "run"]


def shapes(m: dict) -> Dict[str, tuple]:
    """Leaf shapes of the trainer's parameter tree, layers stacked."""
    L, D, V, F = m["num_hidden_layers"], m["hidden_size"], m["vocab_size"], m["intermediate_size"]
    H, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    return {
        "embed": (V, D),
        "final_norm": (D,),
        "layers/ln1": (L, D), "layers/ln2": (L, D),
        "layers/q_norm": (L, hd), "layers/k_norm": (L, hd),
        "layers/wq": (L, D, H * hd), "layers/wk": (L, D, KV * hd),
        "layers/wv": (L, D, KV * hd), "layers/wo": (L, H * hd, D),
        "layers/w_gate": (L, D, F), "layers/w_up": (L, D, F),
        "layers/w_down": (L, F, D),
    }


def _nest(flat: dict) -> dict:
    out: dict = {"layers": {}}
    for k, v in flat.items():
        if k.startswith("layers/"):
            out["layers"][k.split("/", 1)[1]] = v
        else:
            out[k] = v
    return out


def flatten(params: dict) -> Dict[str, jax.Array]:
    out = {k: v for k, v in params.items() if k != "layers"}
    out.update({f"layers/{k}": v for k, v in params["layers"].items()})
    return out


def items(m: dict) -> tuple:
    """The configuration's numbers as a hashable key."""
    return tuple(sorted((k, v) for k, v in m.items() if isinstance(v, (int, float))))


def init(m: dict, seed: int, dtype: str) -> dict:
    """Random weights from the seed (any whole number: its low 31 bits
    key the draw, the rest is folded in), made on the device in one call
    in ``dtype``: N(0, 0.02^2) matrices, the output projections of each
    block scaled by 1 / sqrt(layers), norm scales 0."""
    return _init(items(m), seed & 0x7FFFFFFF, seed >> 31, dtype)


@partial(jax.jit, static_argnums=(0, 3))
def _init(key_items, lo, hi, dtype):
    m = dict(key_items)
    key = jax.random.fold_in(jax.random.key(lo), hi)
    out_scale = 0.02 / m["num_hidden_layers"] ** 0.5
    flat = {}
    for i, (name, shape) in enumerate(sorted(shapes(m).items())):
        if "norm" in name or name.endswith(("ln1", "ln2")):
            flat[name] = jnp.zeros(shape, dtype)
            continue
        scale = out_scale if name.endswith(("wo", "w_down")) else 0.02
        w = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * scale
        flat[name] = w.astype(dtype)
    return _nest(flat)


# -- the model ---------------------------------------------------------------


def _rmsnorm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale)


def _rope(x, theta):
    """Rotary embedding on (B, S, heads, hd): the first and second halves
    of each head are the two coordinates of each rotated pair."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv  # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(m, h, lp):
    B, S, D = h.shape
    H, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    eps = m["rms_norm_eps"]
    a = _rmsnorm(h, lp["ln1"], eps)
    q = jnp.matmul(a, lp["wq"], precision=HIGHEST).reshape(B, S, H, hd)
    k = jnp.matmul(a, lp["wk"], precision=HIGHEST).reshape(B, S, KV, hd)
    v = jnp.matmul(a, lp["wv"], precision=HIGHEST).reshape(B, S, KV, hd)
    q = _rope(_rmsnorm(q, lp["q_norm"], eps), m["rope_theta"])
    k = _rope(_rmsnorm(k, lp["k_norm"], eps), m["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / hd ** 0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST).reshape(B, S, H * hd)
    h = h + jnp.matmul(o, lp["wo"], precision=HIGHEST)
    a = _rmsnorm(h, lp["ln2"], eps)
    g = jnp.matmul(a, lp["w_gate"], precision=HIGHEST)
    u = jnp.matmul(a, lp["w_up"], precision=HIGHEST)
    return h + jnp.matmul(jax.nn.silu(g) * u, lp["w_down"], precision=HIGHEST)


def loss(m: dict, params: dict, tokens, labels, weights) -> jax.Array:
    """sum_b weights_b * mean over positions of -log p(label | prefix)."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    h = p["embed"][tokens]
    h, _ = jax.lax.scan(
        jax.checkpoint(lambda h, lp: (_layer(m, h, lp), None)), h, p["layers"]
    )
    h = _rmsnorm(h, p["final_norm"], m["rms_norm_eps"])
    nll = jax.lax.map(jax.checkpoint(lambda row: _row_nll(p["embed"], *row)), (h, labels))
    return jnp.sum(weights * nll)


def _row_nll(embed, h, labels):
    """One row's mean over positions of -log p(label | prefix)."""
    logits = jnp.matmul(h, embed.T, precision=HIGHEST)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


# -- the coded decode ----------------------------------------------------------


def support(cons: dict) -> List[np.ndarray]:
    """The partitions each ECN stores, in increasing order: the rows of
    ECN j hold these partitions one after another."""
    B = make_code(cons["scheme"], cons["K"], cons["S"], cons["code_seed"])["B"]
    return [np.nonzero(np.abs(B[j]) > 1e-12)[0] for j in range(cons["K"])]


def partitions(cons: dict, rows_per_agent: int) -> np.ndarray:
    """The partition each of an agent's rows belongs to."""
    P = rows_per_agent // (cons["K"] * (cons["S"] + 1))
    return np.repeat(np.concatenate(support(cons)), P)


def row_weights(cons: dict, alive: np.ndarray, rows_per_agent: int) -> np.ndarray:
    """(A, rows) loss weights from the (A, K) alive mask; rows of agent a
    are laid out (ECN j, u-th partition of j, P rows)."""
    K, S = cons["K"], cons["S"]
    B = make_code(cons["scheme"], K, S, cons["code_seed"])["B"]
    sup = support(cons)
    P = rows_per_agent // (K * (S + 1))
    out = []
    for live in np.asarray(alive, bool):
        idx = np.nonzero(live)[0]
        a = np.zeros(K)
        a[idx] = np.linalg.lstsq(B[idx].T, np.ones(K), rcond=None)[0]
        w = [a[j] * B[j, t] / (K * P) for j in range(K) for t in sup[j]]
        out.append(np.repeat(w, P))
    return np.asarray(out, np.float32)


def leaf_norms(after: dict, before=None, scale: float = 1.0, index=None) -> Dict[str, float]:
    """scale * ||after - before|| per leaf in float32 (||after|| without
    ``before``); ``index`` picks one agent's slice of stacked leaves."""
    norms = _leaf_norms(flatten(after), None if before is None else flatten(before),
                        np.float32(scale), index)
    return {k: float(v) for k, v in norms.items()}


@partial(jax.jit, static_argnums=(3,))
def _leaf_norms(after, before, scale, index):
    def norm(a, b=None):
        d = (a if index is None else a[index]).astype(jnp.float32)
        if b is not None:
            d = d - b.astype(jnp.float32)
        return scale * jnp.sqrt(jnp.sum(d * d))

    if before is None:
        return jax.tree.map(norm, after)
    return jax.tree.map(norm, after, before)


# -- three steps ---------------------------------------------------------------


@partial(jax.jit, static_argnums=(0,))
def _value_and_grad(key_items, params, tokens, labels, weights):
    m = dict(key_items)
    return jax.value_and_grad(lambda p: loss(m, p, tokens, labels, weights))(
        jax.tree.map(lambda a: a.astype(jnp.float32), params)
    )


@partial(jax.jit, static_argnums=(0,))
def _loss(key_items, params, tokens, labels, weights):
    return loss(dict(key_items), params, tokens, labels, weights)


@jax.jit
def _x_update(x, y, z, g, rho, tau):
    """eq. (5a), stored."""
    def one(x, y, z, g):
        x32, y32, z32 = (v.astype(jnp.float32) for v in (x, y, z))
        return ((tau * x32 + rho * z32 + y32 - g) / (rho + tau)).astype(x.dtype)

    return jax.tree.map(one, x, y, z, g)


@jax.jit
def _y_update(y, z, xn, rho, gamma):
    """eq. (5b) from the stored x+, stored."""
    def one(y, z, xn):
        y32, z32, xn32 = (v.astype(jnp.float32) for v in (y, z, xn))
        return (y32 + rho * gamma * (z32 - xn32)).astype(y.dtype)

    return jax.tree.map(one, y, z, xn)


@jax.jit
def _z_update(z, x, xn, y, yn, rho, A):
    """eq. (4c) from the stored x, x+, y, y+, stored."""
    def one(z, x, xn, y, yn):
        z32, x32, xn32, y32, yn32 = (v.astype(jnp.float32) for v in (z, x, xn, y, yn))
        return (z32 + ((xn32 - x32) - (yn32 - y32) / rho) / A).astype(z.dtype)

    return jax.tree.map(one, z, x, xn, y, yn)


def _update(x, y, z, g, rho, A, tau, gamma):
    """One agent's eqs. (5a), (5b), (4c), each from the values the one
    before it stored: three programs, so that no compiler carries a value
    to the next equation in more precision than it is stored in."""
    rho, A = np.float32(rho), np.float32(A)
    xn = _x_update(x, y, z, g, rho, tau)
    yn = _y_update(y, z, xn, rho, gamma)
    return xn, yn, _z_update(z, x, xn, y, yn, rho, A)


def run(
    model: dict, cons: dict, seed: int, batches: List[dict], alive: List[np.ndarray],
    store: str, weights_dtype: str, half_batch: bool = False,
) -> dict:
    """The losses of ``len(batches)`` steps, the first committing agent's
    gradient norm per leaf read back from its state after step 1, the
    change of z per leaf after the last step, and the norm of the float32
    gradient of step 1 per leaf.

    ``store`` is the dtype the state is kept in; the weights are made in
    ``weights_dtype`` and then stored in ``store``. ``half_batch`` leaves
    out the rows of the second half of each agent's partitions and
    doubles the weight of the rest: half of the batch left out, the mean
    taken over the rest (a planted fault).
    """
    key_items = items(model)
    A, rho = cons["n_agents"], cons["rho"]
    p0 = jax.tree.map(lambda a: a.astype(store), init(model, seed, weights_dtype))
    zeros = jax.tree.map(jnp.zeros_like, p0)
    x, y, z = [p0] * A, [zeros] * A, p0
    out = {"loss": []}
    for k, (batch, live) in enumerate(zip(batches, alive), start=1):
        rows = batch["tokens"].shape[0] // A
        w = row_weights(cons, live, rows)
        if half_batch:
            w *= 2.0 * (partitions(cons, rows) < cons["K"] // 2)
        tau = np.float32(cons["c_tau"] * np.sqrt(np.float32(k)))
        gamma = np.float32(cons["c_gamma"] / np.sqrt(np.float32(k)))
        act = (k - 1) % A
        losses = []
        for a in range(A):
            sl = slice(a * rows, (a + 1) * rows)
            args = (jnp.asarray(batch["tokens"][sl]), jnp.asarray(batch["labels"][sl]),
                    jnp.asarray(w[a]))
            if a == act:
                val, g = _value_and_grad(key_items, x[a], *args)
            else:
                val = _loss(key_items, x[a], *args)
            losses.append(float(val))
        out["loss"].append(float(np.mean(losses)))
        if k == 1:
            out["ref_grad"] = leaf_norms(g)
        x_old = x[act]
        x[act], y[act], z = _update(x[act], y[act], z, g, rho, A, tau, gamma)
        del g
        if k == 1:
            out["grad_norm"] = leaf_norms(x_old, x[act], float(rho + tau))
        del x_old
    p0 = jax.tree.map(lambda a: a.astype(store), init(model, seed, weights_dtype))
    out["z_change"] = leaf_norms(z, p0)
    return out
