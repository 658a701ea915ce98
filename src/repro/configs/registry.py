"""Architecture registry + input specs (ShapeDtypeStruct stand-ins).

``input_specs(arch, shape)`` builds the exact abstract inputs each step
function is lowered with in the multi-pod dry-run — weak-type-correct,
shardable, and never allocated.
"""

from __future__ import annotations

import importlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import ModelConfig, get_model

from .shapes import InputShape
from .shapes import SHAPES as SHAPES  # re-exported via repro.configs

VIS_PREFIX = 256  # stub vision tokens prepended for VLM configs

_ARCH_MODULES = {
    "mixtral-8x22b": "mixtral_8x22b",
    "phi3.5-moe-42b-a6.6b": "phi35_moe",
    "llama3-405b": "llama3_405b",
    "stablelm-1.6b": "stablelm_1_6b",
    "mamba2-1.3b": "mamba2_1_3b",
    "qwen2-vl-72b": "qwen2_vl_72b",
    "internlm2-20b": "internlm2_20b",
    "qwen3-0.6b": "qwen3_0_6b",
    "recurrentgemma-9b": "recurrentgemma_9b",
    "whisper-medium": "whisper_medium",
    "moonlight-16b-a3b": "moonlight_16b_a3b",
}

ARCHS = tuple(_ARCH_MODULES)


def _module(arch: str):
    if arch not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {list(ARCHS)}")
    return importlib.import_module(f"repro.configs.{_ARCH_MODULES[arch]}")


def get_config(arch: str) -> ModelConfig:
    return _module(arch).CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    return _module(arch).SMOKE


def shape_applicable(cfg: ModelConfig, shape: InputShape) -> Optional[str]:
    """None if (arch, shape) runs; else a reason string for the skip."""
    if shape.name == "long_500k" and not cfg.is_subquadratic:
        return (
            "full quadratic attention at 524k context — skipped per "
            "assignment rules (no sliding-window/block-sparse variant in "
            "the cited config); see DESIGN.md §4"
        )
    return None


def _extra_embeds_spec(cfg: ModelConfig, B: int, dtype) -> Optional[jax.ShapeDtypeStruct]:
    if cfg.modality == "vision_stub":
        return jax.ShapeDtypeStruct((B, VIS_PREFIX, cfg.d_model), dtype)
    if cfg.modality == "audio_stub":
        return jax.ShapeDtypeStruct((B, cfg.encoder_positions, cfg.d_model), dtype)
    return None


def input_specs(cfg: ModelConfig, shape: InputShape) -> dict:
    """Abstract inputs for the step function selected by ``shape.kind``.

    train  -> {"tokens", "labels"[, "extra_embeds"]}
    prefill-> {"tokens"[, "extra_embeds"]}
    decode -> {"cache", "token"}  (cache from eval_shape of init_cache)
    """
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    dt = cfg.jnp_dtype
    if shape.kind == "train":
        batch = {
            "tokens": jax.ShapeDtypeStruct((B, S), i32),
            "labels": jax.ShapeDtypeStruct((B, S), i32),
        }
        ee = _extra_embeds_spec(cfg, B, dt)
        if ee is not None:
            batch["extra_embeds"] = ee
        return batch
    if shape.kind == "prefill":
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
        ee = _extra_embeds_spec(cfg, B, dt)
        if ee is not None:
            batch["extra_embeds"] = ee
        return batch
    if shape.kind == "decode":
        model = get_model(cfg)
        cache = jax.eval_shape(lambda: model.init_cache(B, S))
        return {
            "cache": cache,
            "token": jax.ShapeDtypeStruct((B, 1), i32),
        }
    raise ValueError(shape.kind)


def make_concrete_batch(
    cfg: ModelConfig, shape: InputShape, seed: int = 0
) -> dict:
    """Concrete (host-RNG) batch matching input_specs — smoke tests/examples."""
    rng = np.random.default_rng(seed)
    specs = input_specs(cfg, shape)

    def realize(s):
        if np.issubdtype(s.dtype, np.integer):
            return jnp.asarray(
                rng.integers(0, max(cfg.vocab - 1, 2), size=s.shape, dtype=np.int32)
            )
        return jnp.asarray(rng.standard_normal(s.shape), dtype=s.dtype)

    return jax.tree.map(realize, specs)
