"""Decoder-only transformer LM covering dense / MoE / VLM-backbone configs.

Families served: llama3-405b, internlm2-20b, qwen3-0.6b, stablelm-1.6b
(dense), mixtral-8x22b, phi3.5-moe (MoE), moonlight-16b-a3b (MLA with
DeepSeek-V3 expert layers), qwen2-vl-72b (VLM backbone with a
vision-stub prefix). Layers are parameter-stacked and applied with
``lax.scan`` so a 126-layer model lowers to a compact HLO (critical for the
512-device dry-run on one host).

API (all pure functions of (cfg, params, ...)):
  init(cfg, rng)                           -> params
  loss_fn(cfg, params, batch)              -> (loss, metrics)
  prefill(cfg, params, tokens, ...)        -> (logits_last, cache)
  decode_step(cfg, params, cache, token)   -> (logits, cache)

MLA models with DeepSeek-V3 expert layers (moonlight) hold two stacks,
``dense_layers`` (the leading dense layers) then ``layers`` (the expert
layers), and cache the latent instead of K/V, per stack:
dict(dense_layers=dict(c_kv=(Ld, B, C, r), k_rope=(Ld, B, C, 1, dr)),
layers=dict(...), len=scalar int32).

Cache layout: dict(k=(L, B, C, KV, hd), v=..., len=scalar int32) with
C = min(seq_len, sliding_window). The cache is a ring buffer indexed by
slot = position % C, so decode writes at len % C and prefill rolls its tail
accordingly; validity is count-based (min(len+1, C) slots live).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import (
    _z,
    apply_rope,
    blocked_attention,
    decode_attention,
    layernorm,
    maybe_remat,
    mlp_apply,
    moe_apply,
    moe_share_apply,
    naive_attention,
    rmsnorm,
    _expand_kv,
)

# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------


def init(cfg: ModelConfig, rng: jax.Array) -> dict:
    cfg.validate()
    if cfg.is_deepseek_v3:
        return _init_deepseek_v3(cfg, rng)
    dt = cfg.jnp_dtype
    D, V, L, F = cfg.d_model, cfg.vocab, cfg.n_layers, cfg.d_ff
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    keys = iter(jax.random.split(rng, 32))

    def w(key, *shape, scale=0.02):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dt)

    layers = {
        "ln1": jnp.zeros((L, D), dt),
        "ln2": jnp.zeros((L, D), dt),
        "wq": w(next(keys), L, D, H * hd),
        "wk": w(next(keys), L, D, KV * hd),
        "wv": w(next(keys), L, D, KV * hd),
        "wo": w(next(keys), L, H * hd, D, scale=0.02 / max(L, 1) ** 0.5),
    }
    if cfg.norm == "layernorm":
        layers["ln1_b"] = jnp.zeros((L, D), dt)
        layers["ln2_b"] = jnp.zeros((L, D), dt)
    if cfg.qk_norm:
        layers["q_norm"] = jnp.zeros((L, hd), dt)
        layers["k_norm"] = jnp.zeros((L, hd), dt)
    if cfg.family == "moe":
        E = cfg.n_experts
        layers["router"] = w(next(keys), L, D, E)
        layers["w_gate"] = w(next(keys), L, E, D, F)
        layers["w_up"] = w(next(keys), L, E, D, F)
        layers["w_down"] = w(next(keys), L, E, F, D, scale=0.02 / max(L, 1) ** 0.5)
    else:
        layers["w_gate"] = w(next(keys), L, D, F)
        layers["w_up"] = w(next(keys), L, D, F)
        layers["w_down"] = w(next(keys), L, F, D, scale=0.02 / max(L, 1) ** 0.5)

    params = {
        "embed": w(next(keys), V, D),
        "layers": layers,
        "final_norm": jnp.zeros((D,), dt),
    }
    if cfg.norm == "layernorm":
        params["final_norm_b"] = jnp.zeros((D,), dt)
    if not cfg.tie_embeddings:
        params["lm_head"] = w(next(keys), D, V)
    if cfg.modality == "vision_stub":
        # Projector from the (stub) vision encoder to d_model.
        params["vis_proj"] = w(next(keys), D, D)
    return params


def _router_bias_scale(d_model: int) -> float:
    """The sigmoid router scores' spread at init: logits of unit-RMS
    inputs through N(0, 0.02^2) weights have std 0.02 sqrt(D), and the
    sigmoid's slope at 0 is 1/4. At this scale the correction bias moves
    the choice, so that choosing by s + b and weighting by s differ."""
    return 0.005 * d_model ** 0.5


def _init_deepseek_v3(cfg: ModelConfig, rng: jax.Array) -> dict:
    """MLA layers: ``dense_layers`` (a SwiGLU of d_ff) then ``layers``
    (DeepSeek-V3 expert layers over the experts held)."""
    dt = cfg.jnp_dtype
    D, V, L, H = cfg.d_model, cfg.vocab, cfg.n_layers, cfg.n_heads
    r, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    Ld, Le = cfg.first_dense_layers, cfg.n_layers - cfg.first_dense_layers
    Eh, Fe = cfg.n_experts_held, cfg.d_expert
    Fs = cfg.n_shared_experts * cfg.d_expert
    keys = iter(jax.random.split(rng, 32))
    out = 0.02 / max(L, 1) ** 0.5

    def w(*shape, scale=0.02):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(dt)

    def attn(n):
        return {
            "ln1": jnp.zeros((n, D), dt), "ln2": jnp.zeros((n, D), dt),
            "wq": w(n, D, H * (dn + dr)), "wkv_a": w(n, D, r + dr),
            "kv_norm": jnp.zeros((n, r), dt), "wkv_b": w(n, r, H * (dn + dv)),
            "wo": w(n, H * dv, D, scale=out),
        }

    dense = dict(attn(Ld), w_gate=w(Ld, D, cfg.d_ff), w_up=w(Ld, D, cfg.d_ff),
                 w_down=w(Ld, cfg.d_ff, D, scale=out))
    experts = dict(
        attn(Le),
        router=w(Le, D, cfg.n_experts),
        router_bias=w(Le, cfg.n_experts, scale=_router_bias_scale(D)),
        w_gate=w(Le, Eh, D, Fe), w_up=w(Le, Eh, D, Fe),
        w_down=w(Le, Eh, Fe, D, scale=out),
        shared_gate=w(Le, D, Fs), shared_up=w(Le, D, Fs),
        shared_down=w(Le, Fs, D, scale=out),
    )
    return {
        "embed": w(V, D),
        "dense_layers": dense,
        "layers": experts,
        "final_norm": jnp.zeros((D,), dt),
        "lm_head": w(D, V),
    }


# --------------------------------------------------------------------------
# Blocks
# --------------------------------------------------------------------------


def _norm(cfg, x, scale, bias=None):
    if cfg.norm == "layernorm":
        return layernorm(x, scale, bias)
    return rmsnorm(x, scale, cfg.norm_eps)


def _positions(cfg: ModelConfig, B: int, S: int, offset=0) -> jax.Array:
    pos = jnp.arange(S, dtype=jnp.int32)[None, :] + offset
    pos = jnp.broadcast_to(pos, (B, S))
    if cfg.mrope_sections is not None:
        # Text / stub tokens: all three M-RoPE channels share the position id.
        pos = jnp.broadcast_to(pos[None], (3, B, S))
    return pos


def _attn_qkv(cfg, lp, h, positions):
    B, S, D = h.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = (h @ lp["wq"]).reshape(B, S, H, hd)
    k = (h @ lp["wk"]).reshape(B, S, KV, hd)
    v = (h @ lp["wv"]).reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, lp["q_norm"])
        k = rmsnorm(k, lp["k_norm"])
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction, cfg.mrope_sections)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_fraction, cfg.mrope_sections)
    return q, k, v


def _self_attention(cfg: ModelConfig, lp: dict, x: jax.Array, positions) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Pre-norm attention sub-block. Returns (residual_out, (k, v))."""
    B, S, D = x.shape
    h = _norm(cfg, x, lp["ln1"], lp.get("ln1_b"))
    q, k, v = _attn_qkv(cfg, lp, h, positions)
    if cfg.attn_impl == "pallas":
        # Pallas flash-attention kernel: GQA handled by the kernel's K/V
        # index maps (no materialized head expansion).
        from repro.kernels import flash_attention as _flash

        o = _flash(
            q, k, v, causal=True, window=cfg.sliding_window,
            block_q=cfg.attn_block_q, block_kv=cfg.attn_block_kv,
        )
    else:
        kx = _expand_kv(k, cfg.q_per_kv)
        vx = _expand_kv(v, cfg.q_per_kv)
        o = _causal_attention(cfg, q, kx, vx)
    o = o.reshape(B, S, cfg.n_heads * cfg.d_head) @ lp["wo"]
    return x + o, (k, v)


def _causal_attention(cfg: ModelConfig, q, k, v) -> jax.Array:
    """Blocked (online-softmax) attention for long sequences, else the
    full-matrix path; heads already expanded."""
    S = q.shape[1]
    if S > 1024 and S % cfg.attn_block_q == 0 and S % cfg.attn_block_kv == 0:
        return blocked_attention(
            q, k, v, causal=True, window=cfg.sliding_window,
            block_q=cfg.attn_block_q, block_kv=cfg.attn_block_kv,
        )
    return naive_attention(q, k, v, causal=True, window=cfg.sliding_window)


# -- multi-head latent attention (DeepSeek-V3) ---------------------------------


def _mla_latent(cfg: ModelConfig, lp: dict, h: jax.Array, positions):
    """What a position caches: the normed latent c_kv (B, S, r) and the
    rotated rope key shared by every head (B, S, 1, dr)."""
    r = cfg.kv_lora_rank
    kv_a = h @ lp["wkv_a"]
    c_kv = rmsnorm(kv_a[..., :r], lp["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(kv_a[..., None, r:], positions, cfg.rope_theta, interleaved=True)
    return c_kv, k_rope


def _mla_query(cfg: ModelConfig, lp: dict, h: jax.Array, positions) -> jax.Array:
    """Per head, nope dims then rotated rope dims: (B, S, H, dn + dr)."""
    B, S, _ = h.shape
    dn = cfg.qk_nope_head_dim
    q = (h @ lp["wq"]).reshape(B, S, cfg.n_heads, dn + cfg.qk_rope_head_dim)
    q_rope = apply_rope(q[..., dn:], positions, cfg.rope_theta, interleaved=True)
    return jnp.concatenate([q[..., :dn], q_rope], axis=-1)


def _mla_keys_values(cfg: ModelConfig, lp: dict, c_kv, k_rope):
    """Up-project the latent to per-head keys (nope, then the shared rope
    key) and values: (B, C, H, dn + dr), (B, C, H, dv)."""
    B, C, _ = c_kv.shape
    H, dn = cfg.n_heads, cfg.qk_nope_head_dim
    kv = (c_kv @ lp["wkv_b"]).reshape(B, C, H, dn + cfg.v_head_dim)
    k_rope = jnp.broadcast_to(k_rope, (B, C, H, k_rope.shape[-1]))
    return jnp.concatenate([kv[..., :dn], k_rope], axis=-1), kv[..., dn:]


def _mla_attention(cfg: ModelConfig, lp: dict, x: jax.Array, positions):
    """Pre-norm MLA sub-block: scores over dn + dr dims scaled by
    1/sqrt(dn + dr), causal. Returns (residual_out, (c_kv, k_rope)).
    DESIGN.md §17."""
    B, S, _ = x.shape
    with jax.named_scope("mla"):
        h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
        c_kv, k_rope = _mla_latent(cfg, lp, h, positions)
        k, v = _mla_keys_values(cfg, lp, c_kv, k_rope)
        o = _causal_attention(cfg, _mla_query(cfg, lp, h, positions), k, v)
        o = o.reshape(B, S, cfg.n_heads * cfg.v_head_dim) @ lp["wo"]
    return x + o, (c_kv, k_rope)


def _mla_ffn(cfg: ModelConfig, lp: dict, x: jax.Array, experts: bool):
    """Pre-norm SwiGLU (dense layers) or the expert layer's share, with
    its counters (None for a dense layer)."""
    B, S, D = x.shape
    h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
    if not experts:
        return x + mlp_apply(h, lp, "swiglu"), None
    out, counters = moe_share_apply(
        h.reshape(B * S, D), lp, cfg.n_experts, cfg.experts_per_token,
        cfg.expert_offset, cfg.routed_scale, impl=cfg.moe_impl,
    )
    return x + out.reshape(B, S, D), counters


# (stack, has experts) in the order the layers run
MLA_STACKS = (("dense_layers", False), ("layers", True))


def _forward_deepseek_v3(cfg: ModelConfig, params: dict, tokens: jax.Array):
    """Full-sequence forward of an MLA model; returns (hidden, counters
    of the expert layers: held rows and dropped slots summed over layers,
    max rows the largest on one expert)."""
    B, S = tokens.shape
    x = _embed(cfg, params, tokens)
    positions = _positions(cfg, B, S)
    counters = {}
    for name, experts in MLA_STACKS:

        def block(x, lp, experts=experts):
            x, _ = _mla_attention(cfg, lp, x, positions)
            return _mla_ffn(cfg, lp, x, experts)

        x, c = jax.lax.scan(maybe_remat(block, cfg.remat), x, params[name])
        if c is not None:
            counters = {
                "moe/held_rows": c["held_rows"].sum(),
                "moe/max_rows": c["max_rows"].max(),
                "moe/dropped": c["dropped"].sum(),
            }
    return _norm(cfg, x, params["final_norm"]), counters


def _ffn(cfg: ModelConfig, lp: dict, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    B, S, D = x.shape
    h = _norm(cfg, x, lp["ln2"], lp.get("ln2_b"))
    if cfg.family == "moe":
        out, aux = moe_apply(
            h.reshape(B * S, D),
            {k: lp[k] for k in ("router", "w_gate", "w_up", "w_down")},
            cfg.n_experts,
            cfg.experts_per_token,
            cfg.capacity_factor,
            act=cfg.mlp_act,
            groups=cfg.moe_groups,
            shard_axis=cfg.moe_shard_axis,
        )
        return x + out.reshape(B, S, D), aux
    out = mlp_apply(h, lp, cfg.mlp_act)
    return x + out, jnp.zeros((), jnp.float32)


def _embed(cfg: ModelConfig, params: dict, tokens: jax.Array, extra_embeds=None) -> jax.Array:
    x = params["embed"][tokens]  # (B, S, D)
    if extra_embeds is not None:
        # Modality stub: precomputed patch/frame embeddings replace the
        # leading positions (assignment carve-out; see DESIGN.md §4).
        ee = extra_embeds.astype(x.dtype)
        if "vis_proj" in params:
            ee = ee @ params["vis_proj"]
        Sv = ee.shape[1]
        x = jnp.concatenate([ee, x[:, Sv:]], axis=1)
    return x


def forward(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,  # (B, S) int32
    extra_embeds: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward; returns (hidden (B,S,D), moe_aux scalar)."""
    B, S = tokens.shape
    x = _embed(cfg, params, tokens, extra_embeds)
    positions = _positions(cfg, B, S)

    def block(x, lp):
        x, _ = _self_attention(cfg, lp, x, positions)
        x, aux = _ffn(cfg, lp, x)
        return x, aux

    x, auxs = jax.lax.scan(maybe_remat(block, cfg.remat), x, params["layers"])
    x = _norm(cfg, x, params["final_norm"], params.get("final_norm_b"))
    return x, auxs.sum()


def logits_from_hidden(cfg: ModelConfig, params: dict, hidden: jax.Array) -> jax.Array:
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return hidden @ head


def loss_fn(cfg: ModelConfig, params: dict, batch: dict) -> Tuple[jax.Array, dict]:
    """Causal LM loss. batch: tokens (B,S), labels (B,S) (-100 = ignore),
    optionally extra_embeds (stub modalities) and loss_weights (B,) per-row
    weights (coded-gradient path, see repro.models.losses)."""
    from .losses import lm_loss

    if cfg.is_deepseek_v3:
        hidden, counters = _forward_deepseek_v3(cfg, params, batch["tokens"])
        logits = logits_from_hidden(cfg, params, hidden)
        loss = lm_loss(logits, batch["labels"], batch.get("loss_weights"))
        return loss, dict(counters, nll=loss)
    hidden, aux = forward(
        cfg, params, batch["tokens"], batch.get("extra_embeds")
    )
    logits = logits_from_hidden(cfg, params, hidden)
    loss = lm_loss(logits, batch["labels"], batch.get("loss_weights"))
    total = loss + cfg.router_aux_weight * aux
    return total, {"nll": loss, "moe_aux": aux}


# --------------------------------------------------------------------------
# Serving: prefill + single-token decode
# --------------------------------------------------------------------------


def cache_capacity(cfg: ModelConfig, seq_len: int) -> int:
    if cfg.sliding_window is not None:
        return min(seq_len, cfg.sliding_window)
    return seq_len


def init_cache(cfg: ModelConfig, B: int, seq_len: int) -> dict:
    C = cache_capacity(cfg, seq_len)
    if cfg.is_deepseek_v3:
        dt, Ld = cfg.jnp_dtype, cfg.first_dense_layers
        n = {"dense_layers": Ld, "layers": cfg.n_layers - Ld}
        return dict(
            {name: {"c_kv": jnp.zeros((n[name], B, C, cfg.kv_lora_rank), dt),
                    "k_rope": jnp.zeros((n[name], B, C, 1, cfg.qk_rope_head_dim), dt)}
             for name, _ in MLA_STACKS},
            len=jnp.zeros((), jnp.int32),
        )
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.d_head
    dt = cfg.jnp_dtype
    return {
        "k": jnp.zeros((L, B, C, KV, hd), dt),
        "v": jnp.zeros((L, B, C, KV, hd), dt),
        "len": jnp.zeros((), jnp.int32),  # tokens seen; write slot = len % C
    }


def _to_ring(k: jax.Array, S: int, C: int) -> jax.Array:
    """(B, S, ...) prefill K/V -> (B, C, ...) ring cache with slot = pos % C.

    C > S: pad with empty slots at the end (headroom for decode);
    C <= S: keep the last C entries, rolled into ring position."""
    if C >= S:
        pad = [(0, 0)] * k.ndim
        pad[1] = (0, C - S)
        return jnp.pad(k, pad)
    return jnp.roll(k[:, S - C :], S % C, axis=1)


def prefill(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,  # (B, S)
    extra_embeds: Optional[jax.Array] = None,
    extra_slots: int = 0,  # decode headroom reserved in the cache
) -> Tuple[jax.Array, dict]:
    """Run the full prompt, return last-position logits + the KV cache."""
    B, S = tokens.shape
    x = _embed(cfg, params, tokens, extra_embeds)
    positions = _positions(cfg, B, S)
    C = cache_capacity(cfg, S + extra_slots)
    if cfg.is_deepseek_v3:
        return _prefill_deepseek_v3(cfg, params, x, positions, C)

    def block(x, lp):
        x, (k, v) = _self_attention(cfg, lp, x, positions)
        x, _ = _ffn(cfg, lp, x)
        return x, (_to_ring(k, S, C), _to_ring(v, S, C))

    x, (ks, vs) = jax.lax.scan(block, x, params["layers"])
    x = _norm(cfg, x, params["final_norm"], params.get("final_norm_b"))
    logits = logits_from_hidden(cfg, params, x[:, -1:])
    cache = {"k": ks, "v": vs, "len": jnp.asarray(S, jnp.int32)}
    return logits, cache


def decode_step(
    cfg: ModelConfig,
    params: dict,
    cache: dict,
    token: jax.Array,  # (B, 1) int32
) -> Tuple[jax.Array, dict]:
    """One decode step against the KV cache (ring-buffered if windowed)."""
    if cfg.is_deepseek_v3:
        return _decode_deepseek_v3(cfg, params, cache, token)
    B = token.shape[0]
    x = _embed(cfg, params, token)
    C = cache["k"].shape[2]
    pos_t = cache["len"]  # true position id of this token
    slot = cache["len"] % jnp.asarray(C, jnp.int32)
    positions = jnp.broadcast_to(pos_t[None, None], (B, 1)).astype(jnp.int32)
    if cfg.mrope_sections is not None:
        positions = jnp.broadcast_to(positions[None], (3, B, 1))
    n_valid = jnp.minimum(cache["len"] + 1, C)
    valid = jnp.arange(C)[None, :] < n_valid
    valid = jnp.broadcast_to(valid, (B, C))

    def block(x, layer):
        lp, kc, vc = layer
        h = _norm(cfg, x, lp["ln1"], lp.get("ln1_b"))
        q, k, v = _attn_qkv(cfg, lp, h, positions)
        kc = jax.lax.dynamic_update_slice(kc, k, (_z(slot), slot, _z(slot), _z(slot)))
        vc = jax.lax.dynamic_update_slice(vc, v, (_z(slot), slot, _z(slot), _z(slot)))
        o = decode_attention(q, kc, vc, valid)
        o = o.reshape(B, 1, cfg.n_heads * cfg.d_head) @ lp["wo"]
        x = x + o
        x, _ = _ffn(cfg, lp, x)
        return x, (kc, vc)

    x, (ks, vs) = jax.lax.scan(
        block, x, (params["layers"], cache["k"], cache["v"])
    )
    x = _norm(cfg, x, params["final_norm"], params.get("final_norm_b"))
    logits = logits_from_hidden(cfg, params, x)
    new_cache = {"k": ks, "v": vs, "len": cache["len"] + 1}
    return logits, new_cache


def _prefill_deepseek_v3(cfg: ModelConfig, params: dict, x, positions, C: int):
    """Prefill of an MLA model: the latent cache holds c_kv and the
    rotated rope key of every position."""
    S = x.shape[1]
    cache = {"len": jnp.asarray(S, jnp.int32)}
    for name, experts in MLA_STACKS:

        def block(x, lp, experts=experts):
            x, (c_kv, k_rope) = _mla_attention(cfg, lp, x, positions)
            x, _ = _mla_ffn(cfg, lp, x, experts)
            return x, {"c_kv": _to_ring(c_kv, S, C), "k_rope": _to_ring(k_rope, S, C)}

        x, cache[name] = jax.lax.scan(block, x, params[name])
    x = _norm(cfg, x, params["final_norm"])
    return logits_from_hidden(cfg, params, x[:, -1:]), cache


def _decode_deepseek_v3(cfg: ModelConfig, params: dict, cache: dict, token: jax.Array):
    """One decode step of an MLA model: the token's latent goes into the
    cache, and keys and values are up-projected from the cached latents."""
    B = token.shape[0]
    x = _embed(cfg, params, token)
    C = cache["layers"]["c_kv"].shape[2]
    slot = cache["len"] % jnp.asarray(C, jnp.int32)
    positions = jnp.broadcast_to(cache["len"][None, None], (B, 1)).astype(jnp.int32)
    valid = jnp.broadcast_to(jnp.arange(C)[None, :] < cache["len"] + 1, (B, C))
    new_cache = {"len": cache["len"] + 1}
    for name, experts in MLA_STACKS:

        def block(x, layer, experts=experts):
            lp, lc = layer
            h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
            c_new, kr_new = _mla_latent(cfg, lp, h, positions)
            c_kv = jax.lax.dynamic_update_slice(
                lc["c_kv"], c_new, (_z(slot), slot, _z(slot)))
            k_rope = jax.lax.dynamic_update_slice(
                lc["k_rope"], kr_new, (_z(slot), slot, _z(slot), _z(slot)))
            k, v = _mla_keys_values(cfg, lp, c_kv, k_rope)
            o = decode_attention(_mla_query(cfg, lp, h, positions), k, v, valid)
            x = x + o.reshape(B, 1, cfg.n_heads * cfg.v_head_dim) @ lp["wo"]
            x, _ = _mla_ffn(cfg, lp, x, experts)
            return x, {"c_kv": c_kv, "k_rope": k_rope}

        x, new_cache[name] = jax.lax.scan(block, x, (params[name], cache[name]))
    x = _norm(cfg, x, params["final_norm"])
    return logits_from_hidden(cfg, params, x), new_cache
