"""Pure-jnp oracles for every Pallas kernel (the ground truth in tests).

Each function mirrors the semantics (including accumulation dtype: f32) of
its kernel twin but uses straightforward dense jnp ops, so correctness is
auditable at a glance.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "coded_combine_ref",
    "coded_admm_update_ref",
    "flash_attention_ref",
    "ssd_scan_ref",
    "rglru_scan_ref",
    "expert_gmm_ref",
]


def coded_combine_ref(
    msgs: jax.Array,
    coeffs: jax.Array,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """out = sum_j coeffs[j] * mask[j]>0 * msgs[j] in the accumulation
    dtype (f32, or f64 under x64). msgs (J, n), coeffs/mask (J,).

    ``mask`` where-zeroes dead rows BEFORE the reduction, mirroring the
    kernel's NaN-safe guard (0 * NaN would be NaN, where is not).
    """
    ct = jnp.promote_types(msgs.dtype, jnp.float32)
    m = msgs.astype(ct)
    if mask is not None:
        m = jnp.where(mask[:, None] > 0, m, jnp.zeros((), ct))
    return jnp.tensordot(coeffs.astype(ct), m, axes=1)


def coded_admm_update_ref(
    msgs: jax.Array,  # (J, n) coded gradient messages
    coeffs: jax.Array,  # (J,) decode vector (already includes the 1/K of eq. 6)
    x: jax.Array,  # (n,)
    y: jax.Array,  # (n,)
    z: jax.Array,  # (n,)
    tau: jax.Array,  # scalar tau^k
    rho: float,
    mask: Optional[jax.Array] = None,  # (J,) alive rows (>0)
) -> jax.Array:
    """Fused decode + proximal x-update (eq. 5a):

    G = sum_j coeffs[j] mask[j] msgs[j];
    x+ = (tau x + rho z + y - G) / (rho + tau).
    """
    G = coded_combine_ref(msgs, coeffs, mask)
    ct = G.dtype
    t = tau.astype(ct)
    num = t * x.astype(ct) + rho * z.astype(ct) + y.astype(ct) - G
    return (num / (rho + t)).astype(x.dtype)


def flash_attention_ref(
    q: jax.Array,  # (B, H, Sq, hd)
    k: jax.Array,  # (B, KV, Skv, hd)
    v: jax.Array,  # (B, KV, Skv, hd)
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
) -> jax.Array:
    """Dense attention with GQA head mapping h -> h * KV // H."""
    B, H, Sq, hd = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    kv_idx = jnp.arange(H) * KV // H
    kx = k[:, kv_idx]  # (B, H, Skv, hd)
    vx = v[:, kv_idx]
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), kx.astype(jnp.float32)
    ) / jnp.sqrt(jnp.float32(hd))
    qpos = jnp.arange(Sq) + q_offset
    kpos = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kpos[None] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None] > qpos[:, None] - window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vx.astype(jnp.float32))
    return o.astype(q.dtype)


def ssd_scan_ref(
    x: jax.Array,  # (B, S, H, P)
    dt: jax.Array,  # (B, S, H) f32 post-softplus
    A: jax.Array,  # (H,) f32 negative
    Bm: jax.Array,  # (B, S, N)
    Cm: jax.Array,  # (B, S, N)
    h0: Optional[jax.Array] = None,  # (B, H, P, N)
) -> Tuple[jax.Array, jax.Array]:
    """Sequential SSD recurrence (the mathematical definition):

    h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) B_t^T ;  y_t = h_t C_t.
    Returns (y (B,S,H,P) f32, h_final (B,H,P,N) f32).
    """
    B_, S, H, P = x.shape
    N = Bm.shape[-1]
    h = (
        jnp.zeros((B_, H, P, N), jnp.float32)
        if h0 is None
        else h0.astype(jnp.float32)
    )

    def step(h, t):
        a = jnp.exp(dt[:, t, :, None, None] * A[None, :, None, None])
        xdt = x[:, t].astype(jnp.float32) * dt[:, t, :, None]
        h = a * h + jnp.einsum(
            "bhp,bn->bhpn", xdt, Bm[:, t].astype(jnp.float32)
        )
        y = jnp.einsum("bhpn,bn->bhp", h, Cm[:, t].astype(jnp.float32))
        return h, y

    h, ys = jax.lax.scan(step, h, jnp.arange(S))
    return ys.transpose(1, 0, 2, 3), h


def rglru_scan_ref(
    a: jax.Array,  # (B, S, W) f32 decay in (0, 1]
    b: jax.Array,  # (B, S, W) f32 input term
    h0: Optional[jax.Array] = None,  # (B, W)
) -> Tuple[jax.Array, jax.Array]:
    """h_t = a_t * h_{t-1} + b_t. Returns (h_seq (B,S,W) f32, h_last)."""
    B_, S, W = a.shape
    h = jnp.zeros((B_, W), jnp.float32) if h0 is None else h0.astype(jnp.float32)

    def step(h, t):
        h = a[:, t].astype(jnp.float32) * h + b[:, t].astype(jnp.float32)
        return h, h

    h, hs = jax.lax.scan(step, h, jnp.arange(S))
    return hs.transpose(1, 0, 2), h


def expert_gmm_ref(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """out[r] = lhs[r] @ rhs[g] for the rows r of group g, the rows sorted
    by group (group g owns the next group_sizes[g] rows); rows past the
    last group read 0. lhs (m, k), rhs (G, k, n); accumulated in f32,
    returned in lhs's dtype. Every group's product is taken over all rows
    and masked: G times the kernel's work, for tests."""
    ends = jnp.cumsum(group_sizes)
    gid = jnp.searchsorted(ends, jnp.arange(lhs.shape[0]), side="right")
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32)
    for g in range(rhs.shape[0]):
        y = jnp.dot(lhs, rhs[g], preferred_element_type=jnp.float32)
        out = out + jnp.where((gid == g)[:, None], y, 0.0)
    return out.astype(lhs.dtype)
