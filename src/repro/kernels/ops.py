"""Jitted public wrappers around the Pallas kernels.

Each op pads/reshapes to kernel-legal tiles, dispatches the kernel
(interpret=True automatically off-TPU so the same call sites work in this
CPU container), and restores the caller's layout. These are the functions
the models/runtime call; tests sweep them against `repro.kernels.ref`.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .coded_combine import coded_admm_update_kernel, coded_combine_kernel
from .expert_gmm import grouped_matmul
from .flash_attention import flash_attention_kernel
from .rglru_scan import rglru_scan_kernel
from .ssd_scan import ssd_scan_kernel

__all__ = [
    "coded_combine",
    "coded_admm_update",
    "expert_gmm",
    "fit_block_n",
    "flash_attention",
    "ssd_scan",
    "rglru_scan",
]


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(n: int, mult: int) -> int:
    return (n + mult - 1) // mult * mult


def fit_block_n(n: int, block_n: int = 4096, lane: int = 128) -> int:
    """Largest lane-legal tile <= block_n that avoids gross over-padding.

    The method-kernel step calls the fused ADMM update on flat (p*d,)
    vectors that can be much smaller than the default HBM tile; padding a
    640-float vector to 4096 would 6x the per-step work. Tiles stay
    multiples of the 128-lane vector width (pallas_guide 'Tiling
    Constraints').
    """
    return min(block_n, _pad_to(max(n, 1), lane))


def expert_gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """Grouped expert product: rows of lhs (m, k) sorted by group, group
    g's rows times rhs[g] (k, n); rows past the last group read 0.
    Differentiable (`repro.kernels.expert_gmm`)."""
    return grouped_matmul(_interpret())(lhs, rhs, group_sizes)


# --------------------------------------------------------------------------


def _row_mask(mask, J, dtype) -> jax.Array:
    """Runtime alive mask, defaulting to all-alive. Always a traced (J,)
    array — mask VALUES never force a re-trace, only presence/absence
    (two cached traces at most per shape)."""
    if mask is None:
        return jnp.ones((J,), dtype)
    return jnp.asarray(mask, dtype)


@functools.partial(jax.jit, static_argnames=("block_n",))
def coded_combine(
    msgs: jax.Array,
    coeffs: jax.Array,
    mask: Optional[jax.Array] = None,
    *,
    block_n: int = 4096,
) -> jax.Array:
    """sum_j coeffs[j]*mask[j]*msgs[j] over flat message rows. msgs (J, n).

    ``mask`` (J,) marks alive rows (>0); dead rows are where-zeroed in
    the kernel so garbage (even NaN) in never-arrived messages cannot
    leak into the decode (DESIGN.md §11). None = all rows alive.
    """
    J, n = msgs.shape
    n_pad = _pad_to(n, block_n)
    if n_pad != n:
        msgs = jnp.pad(msgs, ((0, 0), (0, n_pad - n)))
    out = coded_combine_kernel(
        msgs, coeffs, _row_mask(mask, J, jnp.float32),
        block_n=block_n, interpret=_interpret(),
    )
    return out[:n]


@functools.partial(jax.jit, static_argnames=("block_n",))
def coded_admm_update(
    msgs: jax.Array,
    coeffs: jax.Array,
    x: jax.Array,
    y: jax.Array,
    z: jax.Array,
    tau: jax.Array,
    rho: jax.Array,
    mask: Optional[jax.Array] = None,
    *,
    block_n: int = 4096,
) -> jax.Array:
    """Fused decode + eq. (5a) x-update over flat parameter vectors.

    ``rho``/``tau`` are runtime scalars (python floats or traced arrays)
    and ``mask`` (J,) is a runtime alive-row mask: the method-kernel scan
    feeds per-iteration schedule values — decode coefficients, deadline
    truncation masks, step sizes — so none may force a re-trace."""
    J, n = msgs.shape
    n_pad = _pad_to(n, block_n)
    if n_pad != n:
        pad = ((0, 0), (0, n_pad - n))
        msgs = jnp.pad(msgs, pad)
        x = jnp.pad(x, (0, n_pad - n))
        y = jnp.pad(y, (0, n_pad - n))
        z = jnp.pad(z, (0, n_pad - n))
    out = coded_admm_update_kernel(
        msgs, coeffs, _row_mask(mask, J, jnp.float32), x, y, z, tau, rho,
        block_n=block_n, interpret=_interpret(),
    )
    return out[:n]


# --------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "q_offset", "block_q", "block_kv")
)
def flash_attention(
    q: jax.Array,  # (B, Sq, H, hd) — model layout
    k: jax.Array,  # (B, Skv, KV, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_kv: int = 256,
) -> jax.Array:
    """Flash attention in the model's (B, S, H, hd) layout, GQA-aware."""
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    bq = min(block_q, Sq) if Sq % block_q else block_q
    bkv = min(block_kv, Skv) if Skv % block_kv else block_kv
    # Fall back to legal tile sizes for short sequences.
    while Sq % bq:
        bq //= 2
    while Skv % bkv:
        bkv //= 2
    out = flash_attention_kernel(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        causal=causal,
        window=window,
        q_offset=q_offset,
        block_q=bq,
        block_kv=bkv,
        interpret=_interpret(),
    )
    return out.transpose(0, 2, 1, 3)


# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(
    x: jax.Array,  # (B, S, H, P)
    dt: jax.Array,  # (B, S, H)
    A: jax.Array,  # (H,)
    Bm: jax.Array,  # (B, S, N)
    Cm: jax.Array,  # (B, S, N)
    *,
    chunk: int = 128,
) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan; pads S to a chunk multiple with dt=0 identity steps."""
    B, S, H, P = x.shape
    S_pad = _pad_to(S, chunk)
    if S_pad != S:
        pad = S_pad - S
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    y, h = ssd_scan_kernel(
        x,
        dt.astype(jnp.float32),
        A.astype(jnp.float32),
        Bm,
        Cm,
        chunk=chunk,
        interpret=_interpret(),
    )
    return y[:, :S], h


# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("block_s", "block_w"))
def rglru_scan(
    a: jax.Array,  # (B, S, W)
    b: jax.Array,  # (B, S, W)
    h0: Optional[jax.Array] = None,  # (B, W)
    *,
    block_s: int = 256,
    block_w: int = 512,
) -> Tuple[jax.Array, jax.Array]:
    """Linear recurrence h_t = a_t h_{t-1} + b_t (RG-LRU inner scan)."""
    B, S, W = a.shape
    if h0 is not None:
        # Fold initial state into step 0 (kernel starts from zero state).
        b = b.at[:, 0].add(a[:, 0] * h0.astype(b.dtype))
    bs = block_s
    while S % bs:
        bs //= 2
    h, hlast = rglru_scan_kernel(
        a, b, block_s=bs, block_w=block_w, interpret=_interpret()
    )
    return h, hlast
