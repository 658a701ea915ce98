"""Unified model configuration covering all assigned architecture families.

One dataclass drives dense / MoE / SSM / hybrid / VLM / audio backbones; the
per-architecture files in `repro.configs` instantiate it with the exact
assigned hyper-parameters (citations in each file).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp

__all__ = ["ModelConfig"]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    vocab: int
    # attention
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    rope_fraction: float = 1.0  # stablelm-2 partial rotary (0.25)
    mrope_sections: Optional[Tuple[int, int, int]] = None  # qwen2-vl M-RoPE
    sliding_window: Optional[int] = None  # mixtral SWA / rg local attention
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    norm_eps: float = 1e-6  # RMSNorm epsilon
    # multi-head latent attention (DeepSeek-V3 MLA; kv_lora_rank > 0
    # selects the whole DeepSeek-V3 block, ``is_deepseek_v3``): no q-LoRA;
    # keys and values come from a normed latent of kv_lora_rank per
    # position, with one rope key of qk_rope_head_dim shared by every head
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # mlp
    d_ff: int = 0
    mlp_act: str = "swiglu"  # swiglu | geglu | gelu
    # MoE
    n_experts: int = 0
    experts_per_token: int = 0
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    # dispatch token-groups (set = data-axis size to keep the expert
    # scatter shard-local on a mesh; 1 = global dispatch)
    moe_groups: int = 1
    # mesh axis name to anchor the group dim to ("" = let XLA propagate)
    moe_shard_axis: str = ""
    # DeepSeek-V3 expert layers (``is_deepseek_v3``): the first
    # ``first_dense_layers`` layers keep a dense MLP of d_ff, the rest route
    # each token to ``experts_per_token`` of ``n_experts`` experts of width
    # d_expert by sigmoid score + correction bias, weight them by the
    # normalised chosen scores times ``routed_scale``, and add
    # ``n_shared_experts`` shared experts as one SwiGLU of
    # n_shared_experts * d_expert. Dropless: this chip holds experts
    # [expert_offset, expert_offset + experts_held) (0 = all) and computes
    # every token-slot routed to them.
    d_expert: int = 0
    n_shared_experts: int = 0
    first_dense_layers: int = 0
    routed_scale: float = 1.0
    experts_held: int = 0
    expert_offset: int = 0
    # SSM (mamba2 / SSD)
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_expand: int = 2
    ssm_chunk: int = 256
    conv_width: int = 4
    # hybrid (recurrentgemma): layer i is local-attention iff
    # (i % attn_every) == attn_every - 1, else RG-LRU recurrent.
    lru_width: int = 0
    attn_every: int = 0  # 3 => pattern [rec, rec, attn] (1:2)
    # encoder-decoder (whisper)
    encoder_layers: int = 0
    encoder_positions: int = 0  # audio frames after the conv frontend (stub)
    # frontends (stubs per assignment carve-out)
    modality: str = "text"  # text | audio_stub | vision_stub
    # numerics
    dtype: str = "bfloat16"
    # training-time attention implementation: naive | blocked
    attn_block_q: int = 512
    attn_block_kv: int = 1024
    tie_embeddings: bool = False
    # activation checkpointing of the layer scan (training path only):
    #   none | full (recompute everything from layer inputs) | dots
    #   (saveable = dots with no batch dims, XLA's matmul-output policy)
    remat: str = "none"
    # kernel backends: "jnp" (pure-XLA reference paths) or "pallas"
    # (repro.kernels; interpret-mode on CPU, native on TPU)
    attn_impl: str = "jnp"
    ssm_impl: str = "jnp"
    # grouped expert products: "jnp" (kernels.ref twin) or "pallas"
    # (kernels.expert_gmm)
    moe_impl: str = "jnp"

    # ---- derived ---------------------------------------------------------

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def d_head(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.n_heads, 1)

    @property
    def is_deepseek_v3(self) -> bool:
        """kv_lora_rank > 0 selects the whole DeepSeek-V3 block: MLA
        attention in every layer, ``first_dense_layers`` dense layers, then
        expert layers of d_expert. No config here takes one part alone."""
        return self.kv_lora_rank > 0

    @property
    def n_experts_held(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // max(self.n_kv_heads, 1)

    @property
    def d_inner(self) -> int:
        """SSM inner width."""
        return self.ssm_expand * self.d_model

    @property
    def is_subquadratic(self) -> bool:
        """Eligible for the long_500k decode shape."""
        return (
            self.family in ("ssm", "hybrid")
            or self.sliding_window is not None
        )

    @property
    def has_decoder(self) -> bool:
        """False only for encoder-only models (none assigned)."""
        return True

    def validate(self) -> None:
        if self.family in ("dense", "moe", "vlm", "audio"):
            assert self.n_heads > 0 and self.d_ff >= 0
            assert self.n_heads % max(self.n_kv_heads, 1) == 0
        if self.family == "moe":
            assert 0 < self.experts_per_token <= self.n_experts
        if self.is_deepseek_v3:
            assert self.d_expert > 0 and self.n_layers > self.first_dense_layers
            assert 0 <= self.expert_offset
            assert self.expert_offset + self.n_experts_held <= self.n_experts
        if self.family == "ssm":
            assert self.ssm_state > 0 and self.ssm_heads > 0
        if self.family == "hybrid":
            assert self.attn_every > 1 and self.lru_width > 0
        if self.family == "audio":
            assert self.encoder_layers > 0

    def param_count(self) -> int:
        """Analytic parameter count (drives roofline MODEL_FLOPS = 6 N D)."""
        D, F, V, L = self.d_model, self.d_ff, self.vocab, self.n_layers
        n = V * D  # embedding
        if not self.tie_embeddings:
            n += V * D
        if self.family == "ssm":
            di, ns, H = self.d_inner, self.ssm_state, self.ssm_heads
            conv_dim = di + 2 * ns  # x, B, C share the conv
            per = (
                D * (2 * di + 2 * ns + H)  # in_proj (z, x, B, C, dt)
                + conv_dim * self.conv_width
                + di * D  # out_proj
                + di  # gated norm scale
                + 2 * H  # A_log, dt_bias... (approx: D params)
                + D  # pre-norm
            )
            return n + L * per
        if self.is_deepseek_v3:
            return n + self._mla_moe_param_count()
        hd, nh, nkv = self.d_head, self.n_heads, self.n_kv_heads
        attn = D * nh * hd + 2 * D * nkv * hd + nh * hd * D
        if self.qk_norm:
            attn += 2 * hd
        if self.mlp_act in ("swiglu", "geglu"):
            mlp = 3 * D * F
        else:
            mlp = 2 * D * F
        norms = 2 * D
        if self.family == "moe":
            mlp = self.n_experts * 3 * D * F + D * self.n_experts
        if self.family == "hybrid":
            n_attn = L // self.attn_every
            n_rec = L - n_attn
            W = self.lru_width
            rec = 2 * D * W + W * self.conv_width + W * D + 4 * W
            return n + n_attn * (attn + mlp + norms) + n_rec * (rec + mlp + norms) + D
        if self.family == "audio":
            enc = self.encoder_layers * (attn + 2 * D * F + norms)
            dec = L * (attn + attn + 2 * D * F + 3 * D)  # self+cross attn
            return n + enc + dec + self.encoder_positions * D
        return n + L * (attn + mlp + norms) + D

    def _mla_moe_param_count(self) -> int:
        """Layers and final norm of an MLA model with DeepSeek-V3 expert
        layers, counting the experts held."""
        D, H, r = self.d_model, self.n_heads, self.kv_lora_rank
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        attn = (
            D * H * qk
            + D * (r + self.qk_rope_head_dim)
            + r
            + r * H * (self.qk_nope_head_dim + self.v_head_dim)
            + H * self.v_head_dim * D
        )
        Ld = self.first_dense_layers
        dense = attn + 3 * D * self.d_ff + 2 * D
        shared = 3 * D * self.n_shared_experts * self.d_expert
        expert = attn + shared + D * self.n_experts + self.n_experts
        expert += self.n_experts_held * 3 * D * self.d_expert + 2 * D
        return Ld * dense + (self.n_layers - Ld) * expert + D

    def active_param_count(self) -> int:
        """Active params per token (MoE: only top-k experts)."""
        if self.is_deepseek_v3:
            routed = self.n_experts_held * 3 * self.d_model * self.d_expert
            active = self.experts_per_token * 3 * self.d_model * self.d_expert
            L = self.n_layers - self.first_dense_layers
            return self.param_count() - L * (routed - active)
        if self.family != "moe":
            return self.param_count()
        D, F, L = self.d_model, self.d_ff, self.n_layers
        total = self.param_count()
        moe_all = L * self.n_experts * 3 * D * F
        moe_active = L * self.experts_per_token * 3 * D * F
        return total - moe_all + moe_active
