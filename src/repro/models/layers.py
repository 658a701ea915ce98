"""Shared neural layers (pure functions over param pytrees).

Everything is written against jnp + lax only — no flax/haiku — so the same
functions trace under jit/pjit on any mesh. Shapes use the conventions:

  B batch, S sequence, D d_model, H query heads, KV kv heads, hd head_dim,
  F d_ff, E experts, C expert capacity, W attention window.

Attention supports:
  - GQA (H != KV) via logical head grouping,
  - optional qk-norm (qwen3),
  - partial rotary (stablelm-2, fraction of head_dim rotated),
  - M-RoPE (qwen2-vl, 3-section rotary over (t, h, w) position ids),
  - causal and sliding-window masks,
  - a blocked (flash-style, online-softmax) path for long sequences that
    mirrors the Pallas kernel in `repro.kernels.flash_attention`,
  - single-token decode against a (ring-buffered) KV cache.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layernorm(
    x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5
) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


# --------------------------------------------------------------------------
# Rotary embeddings
# --------------------------------------------------------------------------


def rope_frequencies(
    rot_dim: int, theta: float, positions: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables. positions: (..., S) int -> (..., S, rot_dim/2)."""
    inv = 1.0 / (
        theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim)
    )
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(
    x: jax.Array,  # (B, S, H, hd)
    positions: jax.Array,  # (B, S) or (3, B, S) for M-RoPE
    theta: float,
    fraction: float = 1.0,
    mrope_sections: Optional[Tuple[int, int, int]] = None,
    interleaved: bool = False,
) -> jax.Array:
    """Rotate pairs of the leading ``fraction`` of each head by position.

    By default the first and second halves of the rotated slice are the
    two coordinates of each pair. ``interleaved`` pairs dimensions (2i,
    2i+1) instead and returns the rotated slice in halves order, as
    DeepSeek-V3's modeling code does (it de-interleaves, then rotates
    halves)."""
    hd = x.shape[-1]
    rot = int(hd * fraction)
    rot -= rot % 2
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    if interleaved:
        x_rot = jnp.concatenate([x_rot[..., 0::2], x_rot[..., 1::2]], axis=-1)

    if mrope_sections is not None:
        # Qwen2-VL M-RoPE: the rot/2 frequency slots are split into three
        # sections driven by (temporal, height, width) position ids.
        sec = mrope_sections
        assert sum(sec) == rot // 2, (sec, rot)
        cos3, sin3 = rope_frequencies(rot, theta, positions)  # (3,B,S,rot/2)
        splits = [sec[0], sec[0] + sec[1]]  # static split points
        cos = jnp.concatenate(
            [c for c in (jnp.split(cos3[i], splits, axis=-1)[i] for i in range(3))],
            axis=-1,
        )
        sin = jnp.concatenate(
            [s for s in (jnp.split(sin3[i], splits, axis=-1)[i] for i in range(3))],
            axis=-1,
        )
    else:
        cos, sin = rope_frequencies(rot, theta, positions)  # (B,S,rot/2)

    cos = cos[..., None, :]  # (B, S, 1, rot/2)
    sin = sin[..., None, :]
    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    y = jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)
    return jnp.concatenate([y, x_pass], axis=-1) if rot < hd else y


# --------------------------------------------------------------------------
# Attention
# --------------------------------------------------------------------------


def _expand_kv(k: jax.Array, q_per_kv: int) -> jax.Array:
    """(B, S, KV, hd) -> (B, S, KV*q_per_kv, hd) by repeat (GQA)."""
    if q_per_kv == 1:
        return k
    return jnp.repeat(k, q_per_kv, axis=2)


def naive_attention(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Skv, H, hd)  (already GQA-expanded)
    v: jax.Array,  # (B, Skv, H, vd)  (vd may differ from hd: MLA)
    causal: bool,
    window: Optional[int] = None,
    q_offset: int = 0,
) -> jax.Array:
    """Reference full-matrix attention (used for short sequences + oracles).
    Scores are scaled by 1/sqrt(hd); the output has v's head width."""
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    qpos = jnp.arange(Sq) + q_offset
    kpos = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def blocked_attention(
    q: jax.Array,  # (B, S, H, hd)
    k: jax.Array,  # (B, S, H, hd)
    v: jax.Array,  # (B, S, H, vd)  (vd may differ from hd: MLA)
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 512,
    block_kv: int = 1024,
) -> jax.Array:
    """Flash-style online-softmax attention in pure jnp (O(S*block) memory).

    Mirrors the Pallas kernel (repro.kernels.flash_attention); this is the
    lowering-friendly path used for long-sequence prefill/training. Blocks
    fully outside the causal/window band are still *computed* here (masked) —
    the Pallas kernel skips them; XLA's scan keeps memory bounded either way.
    """
    B, S, H, hd = q.shape
    vd = v.shape[-1]
    assert S % block_q == 0 and S % block_kv == 0, (S, block_q, block_kv)
    nq, nk = S // block_q, S // block_kv
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)

    qb = q.reshape(B, nq, block_q, H, hd).transpose(1, 0, 3, 2, 4)
    kb = k.reshape(B, nk, block_kv, H, hd).transpose(1, 0, 3, 2, 4)
    vb = v.reshape(B, nk, block_kv, H, vd).transpose(1, 0, 3, 2, 4)

    def per_qblock(qi, qblk):  # qblk (B, H, bq, hd)
        q32 = qblk.astype(jnp.float32) * scale
        qpos = qi * block_q + jnp.arange(block_q)

        def kv_step(carry, inp):
            acc, m, l = carry
            ki, kblk, vblk = inp
            kpos = ki * block_kv + jnp.arange(block_kv)
            s = jnp.einsum(
                "bhqd,bhkd->bhqk", q32, kblk.astype(jnp.float32)
            )
            mask = jnp.ones((block_q, block_kv), dtype=bool)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window is not None:
                mask &= kpos[None, :] > qpos[:, None] - window
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + p.sum(-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p, vblk.astype(jnp.float32)
            )
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((B, H, block_q, vd), jnp.float32)
        m0 = jnp.full((B, H, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, block_q), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0), (jnp.arange(nk), kb, vb)
        )
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out  # (B, H, bq, vd)

    out = jax.lax.map(
        lambda args: per_qblock(*args), (jnp.arange(nq), qb)
    )  # (nq, B, H, bq, vd)
    out = out.transpose(1, 0, 3, 2, 4).reshape(B, S, H, vd)
    return out.astype(q.dtype)


def decode_attention(
    q: jax.Array,  # (B, 1, H, hd)
    k_cache: jax.Array,  # (B, C, KV, hd) — C = cache length (maybe ring)
    v_cache: jax.Array,  # (B, C, KV, vd)
    valid: jax.Array,  # (B, C) bool — which cache slots participate
) -> jax.Array:
    """Single-token decode attention over a (possibly ring-buffered) cache.

    The cache stays in its storage dtype: the dots accumulate in f32 via
    ``preferred_element_type`` instead of materializing an f32 copy of the
    whole cache (which would double decode HBM traffic — decode is the
    bandwidth-bound step; see EXPERIMENTS.md §Perf decode note)."""
    B, C, KV, hd = k_cache.shape
    H = q.shape[2]
    # Heads are ordered group-major: q head h belongs to kv head h // (H/KV)
    # (consistent with _expand_kv's jnp.repeat).
    qg = q[:, 0].reshape(B, KV, H // KV, hd)  # (B, KV, qpk, hd)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    qs = (qg.astype(jnp.float32) * scale).astype(k_cache.dtype)
    s = jnp.einsum(
        "bgqd,bcgd->bgqc", qs, k_cache, preferred_element_type=jnp.float32
    )
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bgqc,bcgd->bgqd",
        p.astype(v_cache.dtype),
        v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, 1, H, v_cache.shape[-1]).astype(q.dtype)


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------


def mlp_apply(x: jax.Array, p: dict, act: str) -> jax.Array:
    """Gated or plain MLP. p: w_gate/w_up/w_down (gated) or w_in/w_out."""
    if act in ("swiglu", "geglu"):
        g = x @ p["w_gate"]
        u = x @ p["w_up"]
        h = (jax.nn.silu(g) if act == "swiglu" else jax.nn.gelu(g)) * u
        return h @ p["w_down"]
    h = jax.nn.gelu(x @ p["w_in"])
    return h @ p["w_out"]


# --------------------------------------------------------------------------
# Mixture of Experts (capacity-based dropless-ish dispatch)
# --------------------------------------------------------------------------


def moe_apply(
    x: jax.Array,  # (T, D) flattened tokens
    p: dict,  # router (D, E), w_gate/w_up (E, D, F), w_down (E, F, D)
    n_experts: int,
    top_k: int,
    capacity_factor: float,
    act: str = "swiglu",
    groups: int = 1,
    shard_axis: str = "",
) -> Tuple[jax.Array, jax.Array]:
    """Top-k token-choice routing with per-expert capacity.

    Returns (out (T, D), aux_loss scalar). Sort-free dispatch: position of a
    token within its expert's buffer comes from a cumsum over the one-hot
    assignment; tokens past capacity are dropped (residual passes through).

    ``groups > 1`` dispatches per token-group with per-group capacity C/G
    (an explicit leading G dim on every intermediate). With ``shard_axis``
    set to the mesh data axis, every G-major intermediate — including the
    (G, E, C, D) dispatch buffers — is pinned to that axis and the expert
    weights are pinned replicated-over-data / TP-over-model, so the
    dispatch stays shard-local and the expert matmuls never contract over
    a data-sharded dimension (both pathologies cost TBs of all-reduce per
    step otherwise; EXPERIMENTS.md §Perf pair 1 iters 2-5). Capacity is
    enforced per group, a standard locality/quality trade.
    """
    T, D = x.shape
    E, k, G = n_experts, top_k, groups
    assert T % G == 0, (T, G)
    Tg = T // G
    C = int(max(1, capacity_factor * Tg * k / E))
    C = min(C, Tg)

    if shard_axis:
        from jax.sharding import PartitionSpec as _P

        def wsc(t, *spec):
            return jax.lax.with_sharding_constraint(t, _P(*spec))
    else:
        def wsc(t, *spec):
            return t

    # "pod+data" pins the group dim over multiple mesh axes (multi-pod)
    ax = tuple(shard_axis.split("+")) if shard_axis else None
    xg = wsc(x.reshape(G, Tg, D), ax, None, None)

    logits = jnp.einsum(
        "gtd,de->gte", xg.astype(jnp.float32), p["router"].astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)  # (G, Tg, E)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)  # (G, Tg, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # Load-balance aux loss (Switch-style): E * sum_e f_e * p_e.
    me = jnp.mean(probs, axis=(0, 1))  # mean router prob per expert
    assign = jax.nn.one_hot(gate_idx[..., 0], E)  # top-1 fraction
    fe = jnp.mean(assign, axis=(0, 1))
    aux = E * jnp.sum(fe * me)

    # Dispatch positions within each group: slot position of a token in its
    # expert's buffer = running count of prior slots for that expert.
    flat_e = gate_idx.reshape(G, Tg * k)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (G, Tg*k, E)
    pos_in_e = jnp.cumsum(onehot, axis=1) - onehot  # exclusive cumsum per g
    pos = jnp.take_along_axis(pos_in_e, flat_e[..., None], axis=2)[..., 0]
    keep = pos < C  # (G, Tg*k)
    tok_idx = jnp.arange(Tg * k) // k
    e_safe = jnp.where(keep, flat_e, 0)
    p_safe = jnp.where(keep, pos, C - 1)
    vals = jnp.where(keep[..., None], xg[:, tok_idx], 0).astype(x.dtype)

    def scat(e_s, p_s, v):  # per group: (Tg*k,), (Tg*k,), (Tg*k, D)
        return jnp.zeros((E, C, D), x.dtype).at[e_s, p_s].add(v, mode="drop")

    buf = jax.vmap(scat)(e_safe, p_safe, vals)  # (G, E, C, D)
    buf = wsc(buf, ax, None, None, None)

    # Expert matmuls: weights replicated over data (FSDP gather happens on
    # the 100MB weight shards, not the multi-GB outputs), F TP over model.
    w_gate = wsc(p["w_gate"], None, None, "model" if ax else None)
    w_up = wsc(p["w_up"], None, None, "model" if ax else None)
    w_down = wsc(p["w_down"], None, "model" if ax else None, None)
    g = jnp.einsum("gecd,edf->gecf", buf, w_gate)
    u = jnp.einsum("gecd,edf->gecf", buf, w_up)
    h = (jax.nn.silu(g) if act == "swiglu" else jax.nn.gelu(g)) * u
    h = wsc(h, ax, None, None, "model" if ax else None)
    y = jnp.einsum("gecf,efd->gecd", h, w_down)  # (G, E, C, D)
    y = wsc(y, ax, None, None, None)

    # Combine: gather each routed slot's output, weight by gate value.
    def gath(yb, e_s, p_s):  # per group
        return yb[e_s, p_s]  # (Tg*k, D)

    slot_out = jax.vmap(gath)(y, e_safe, p_safe)
    slot_out = jnp.where(keep[..., None], slot_out, 0)
    w = gate_vals.reshape(G, Tg * k, 1).astype(slot_out.dtype)

    def comb(so):  # per group: (Tg*k, D) -> (Tg, D)
        return jnp.zeros((Tg, D), so.dtype).at[tok_idx].add(so)

    out = jax.vmap(comb)(slot_out * w)  # (G, Tg, D)
    out = wsc(out, ax, None, None)
    return out.reshape(T, D).astype(x.dtype), aux.astype(jnp.float32)


def moe_share_apply(
    x: jax.Array,  # (T, D) flattened tokens
    p: dict,  # router (D, E), router_bias (E,), w_gate/w_up (Eh, D, F),
    # w_down (Eh, F, D), shared_gate/shared_up (D, Fs), shared_down (Fs, D)
    n_experts: int,
    top_k: int,
    expert_offset: int,
    routed_scale: float,
    impl: str = "jnp",
) -> Tuple[jax.Array, dict]:
    """A DeepSeek-V3 expert layer's share on a chip holding experts
    [expert_offset, expert_offset + Eh) of ``n_experts``, dropless.

    Routing is over all experts: sigmoid scores s, the top ``top_k`` of
    s + router_bias chosen (the bias only chooses, so its gradient is 0),
    weights s_chosen / sum(s_chosen) * routed_scale. The
    token-slots (T * top_k) are sorted by expert; those of held experts
    come first, one group per held expert, in a buffer sized for the worst
    case (every slot held), so no slot is ever dropped. The grouped SwiGLU
    products (``impl``: "pallas" for `repro.kernels.expert_gmm`, "jnp" for
    its twin) compute the held groups only; each slot's result goes back
    to its token times its weight, and the shared expert is added. What
    the experts held elsewhere would add is not computed.

    Returns (out (T, D), counters): token-slots routed to the held
    experts (``held_rows``), the most on one held expert (``max_rows``),
    and held slots left uncomputed (``dropped``: 0, since the buffer
    holds every slot).
    DESIGN.md §18.
    """
    T, D = x.shape
    Eh = p["w_gate"].shape[0]
    if impl == "pallas":
        from repro.kernels.ops import expert_gmm as gmm
    else:
        from repro.kernels.ref import expert_gmm_ref as gmm

    with jax.named_scope("moe.route"):
        logits = jnp.dot(
            x.astype(jnp.float32), p["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        s = jax.nn.sigmoid(logits)  # (T, E)
        _, idx = jax.lax.top_k(s + p["router_bias"].astype(jnp.float32), top_k)
        w = jnp.take_along_axis(s, idx, axis=1)
        w = w / jnp.sum(w, axis=1, keepdims=True) * routed_scale

    with jax.named_scope("moe.dispatch"):
        e = idx.reshape(-1) - expert_offset  # (T*k,) slot -> local expert
        held = (e >= 0) & (e < Eh)
        key = jnp.where(held, e, Eh).astype(jnp.int32)
        order = jnp.argsort(key, stable=True)  # held slots first, by expert
        group_sizes = jnp.zeros((Eh + 1,), jnp.int32).at[key].add(1)[:Eh]
        rows = x[order // top_k]  # (T*k, D)

    with jax.named_scope("moe.experts"):
        g = gmm(rows, p["w_gate"], group_sizes)
        u = gmm(rows, p["w_up"], group_sizes)
        y = gmm(jax.nn.silu(g) * u, p["w_down"], group_sizes)  # (T*k, D)

    with jax.named_scope("moe.combine"):
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        slot_out = y[inverse].reshape(T, top_k, D)  # held slots; others 0
        out = jnp.einsum(
            "tkd,tk->td", slot_out, w.astype(slot_out.dtype),
            preferred_element_type=jnp.float32,
        )

    with jax.named_scope("moe.shared"):
        shared = mlp_apply(
            x, {"w_gate": p["shared_gate"], "w_up": p["shared_up"],
                "w_down": p["shared_down"]}, "swiglu",
        )

    counters = {
        "held_rows": jnp.sum(held.astype(jnp.int32)),
        "max_rows": jnp.max(group_sizes),
        # The buffer holds all T * top_k slots: none can be left out.
        "dropped": jnp.zeros((), jnp.int32),
    }
    return out.astype(x.dtype) + shared, counters


def _z(like: jax.Array) -> jax.Array:
    """Zero index scalar matching ``like``'s dtype (x64-safe dus indices)."""
    return jnp.zeros((), like.dtype)


def maybe_remat(fn, remat: str):
    """Wrap a scan body in jax.checkpoint per the config policy.

    "full" saves only layer boundaries (max recompute, min memory);
    "dots" keeps matmul outputs (recomputes cheap elementwise/softmax only).
    """
    if remat == "none":
        return fn
    if remat == "full":
        return jax.checkpoint(fn)
    if remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    raise ValueError(f"unknown remat policy {remat!r}")
