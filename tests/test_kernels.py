"""Pallas kernel validation: sweep shapes/dtypes vs. the pure-jnp oracles.

All kernels execute in interpret mode on CPU (the container has no TPU);
interpret mode runs the same kernel body Python, so BlockSpec indexing,
scratch carry and masking logic are what is being validated.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (
    coded_admm_update,
    coded_combine,
    flash_attention,
    rglru_scan,
    ssd_scan,
)
from repro.kernels.ref import (
    coded_admm_update_ref,
    coded_combine_ref,
    flash_attention_ref,
    rglru_scan_ref,
    ssd_scan_ref,
)

TOL = {
    jnp.float32: dict(rtol=1e-5, atol=1e-5),
    jnp.bfloat16: dict(rtol=2e-2, atol=2e-2),
}


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# --------------------------------------------------------------------------
# coded_combine / coded_admm_update
# --------------------------------------------------------------------------


@pytest.mark.parametrize("J,n", [(3, 4096), (5, 5000), (16, 12_288), (2, 17)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_coded_combine(J, n, dtype):
    k1, k2 = jax.random.split(jax.random.key(J * n))
    msgs = _rand(k1, (J, n), dtype)
    coeffs = _rand(k2, (J,), jnp.float32)
    out = coded_combine(msgs, coeffs)
    ref = coded_combine_ref(msgs, coeffs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL[dtype])


@pytest.mark.parametrize("J,n", [(3, 4096), (4, 9999)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_coded_admm_update(J, n, dtype):
    keys = jax.random.split(jax.random.key(J + n), 5)
    msgs = _rand(keys[0], (J, n), dtype)
    coeffs = _rand(keys[1], (J,), jnp.float32)
    x = _rand(keys[2], (n,), dtype)
    y = _rand(keys[3], (n,), dtype)
    z = _rand(keys[4], (n,), dtype)
    tau = jnp.asarray(2.5, jnp.float32)
    rho = 1.0
    out = coded_admm_update(msgs, coeffs, x, y, z, tau, rho)
    ref = coded_admm_update_ref(msgs, coeffs, x, y, z, tau, rho)
    assert out.dtype == x.dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **TOL[dtype]
    )


def test_coded_combine_mask_guards_dead_rows():
    """Dead message rows are where-zeroed BEFORE the reduction: NaN/Inf
    garbage in never-arrived rows must not pollute the decode (a plain
    0 * NaN multiply would)."""
    J, n = 4, 1000
    rng = np.random.default_rng(0)
    msgs = rng.standard_normal((J, n)).astype(np.float32)
    msgs[2] = np.nan  # ECN 2 never responded; its buffer is garbage
    msgs[3] = np.inf
    coeffs = rng.standard_normal(J).astype(np.float32)
    mask = jnp.asarray([1.0, 1.0, 0.0, 0.0], jnp.float32)
    out = coded_combine(jnp.asarray(msgs), jnp.asarray(coeffs), mask)
    ref = coded_combine_ref(jnp.asarray(msgs), jnp.asarray(coeffs), mask)
    expect = coeffs[0] * msgs[0] + coeffs[1] * msgs[1]
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-6
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_coded_admm_update_mask_parity(dtype):
    """Kernel == oracle for masked decode patterns (deadline truncation)."""
    J, n = 6, 5000
    keys = jax.random.split(jax.random.key(17), 5)
    msgs = _rand(keys[0], (J, n), dtype)
    coeffs = _rand(keys[1], (J,), jnp.float32)
    x = _rand(keys[2], (n,), dtype)
    y = _rand(keys[3], (n,), dtype)
    z = _rand(keys[4], (n,), dtype)
    tau = jnp.asarray(1.3, jnp.float32)
    mask = jnp.asarray([1, 0, 1, 1, 0, 1], jnp.float32)
    out = coded_admm_update(msgs, coeffs, x, y, z, tau, 0.9, mask)
    ref = coded_admm_update_ref(msgs, coeffs, x, y, z, tau, 0.9, mask)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **TOL[dtype]
    )


@pytest.mark.parametrize("family,K,S", [("mds", 6, 2), ("approx", 6, 2)])
def test_coded_kernels_real_family_patterns(family, K, S):
    """The new families' actual decode vectors — including a
    deadline-truncated sub-R pattern for the partial-recovery family —
    drive the fused kernel to the same update as the dense oracle and
    the analytic eq. (5a)."""
    from repro.core.coding import make_code

    code = make_code(family, K, S, seed=0)
    n = 700
    rng = np.random.default_rng(5)
    gbar = rng.standard_normal((K, n)).astype(np.float32)
    msgs = (code.B.astype(np.float32) @ gbar).astype(np.float32)
    patterns = [np.arange(K) >= S]  # an exact-at-R alive set
    if code.min_responses < code.R:
        trunc = np.zeros(K, dtype=bool)  # deadline caught r_min + 1 rows
        trunc[: code.min_responses + 1] = True
        patterns.append(trunc)
    for alive in patterns:
        a = code.decode_vector(alive).astype(np.float32)
        x = rng.standard_normal(n).astype(np.float32)
        y = rng.standard_normal(n).astype(np.float32)
        z = rng.standard_normal(n).astype(np.float32)
        tau, rho = 1.7, 0.8
        G = (a @ msgs) / K
        expect = (tau * x + rho * z + y - G) / (rho + tau)
        args = (
            jnp.asarray(msgs), jnp.asarray(a / K), jnp.asarray(x),
            jnp.asarray(y), jnp.asarray(z), jnp.asarray(tau), rho,
            jnp.asarray(alive, jnp.float32),
        )
        out = coded_admm_update(*args)
        ref = coded_admm_update_ref(*args)
        np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-6)


def test_coded_kernels_f64_interpret_parity():
    """Under x64 the interpret-mode kernels accumulate in f64 end to end
    (the convergence suite's precision floor): parity vs the oracle at
    f64-tight tolerance."""
    with jax.enable_x64(True):
        J, n = 5, 3000
        rng = np.random.default_rng(7)
        msgs = jnp.asarray(rng.standard_normal((J, n)))
        coeffs = jnp.asarray(rng.standard_normal(J))
        x, y, z = (jnp.asarray(rng.standard_normal(n)) for _ in range(3))
        mask = jnp.asarray([1.0, 1.0, 0.0, 1.0, 1.0])
        tau = jnp.asarray(2.2)
        assert msgs.dtype == jnp.float64
        out_c = coded_combine(msgs, coeffs, mask)
        ref_c = coded_combine_ref(msgs, coeffs, mask)
        assert out_c.dtype == jnp.float64
        np.testing.assert_allclose(
            np.asarray(out_c), np.asarray(ref_c), rtol=1e-12, atol=1e-12
        )
        out_u = coded_admm_update(msgs, coeffs, x, y, z, tau, 0.7, mask)
        ref_u = coded_admm_update_ref(msgs, coeffs, x, y, z, tau, 0.7, mask)
        assert out_u.dtype == jnp.float64
        np.testing.assert_allclose(
            np.asarray(out_u), np.asarray(ref_u), rtol=1e-12, atol=1e-12
        )


def test_runtime_coeffs_and_mask_do_not_retrace():
    """Decode coefficients and deadline masks are DATA: feeding new
    values (new straggler patterns, new deadlines) must reuse the one
    compiled trace — the property that lets a whole code_frontier sweep
    share a single dispatch."""
    J, n = 4, 4096
    key = jax.random.key(3)
    msgs = _rand(key, (J, n), jnp.float32)
    x = y = z = _rand(key, (n,), jnp.float32)
    tau = jnp.asarray(1.0, jnp.float32)

    def call(c, m):
        return coded_admm_update(
            msgs, jnp.asarray(c, jnp.float32), x, y, z, tau, 1.0,
            jnp.asarray(m, jnp.float32),
        )

    call([1.0, 2.0, 3.0, 4.0], [1, 1, 1, 1])
    size0 = coded_admm_update._cache_size()
    call([0.5, 0.0, -1.0, 2.0], [1, 0, 1, 1])  # new pattern
    call([9.0, 9.0, 9.0, 9.0], [0, 0, 1, 0])  # deadline truncation
    assert coded_admm_update._cache_size() == size0


def test_coded_admm_update_matches_scan_admm_equation():
    """The fused kernel must equal the decode+x-update used in core.admm."""
    from repro.core.coding import paper_fig2_code

    code = paper_fig2_code()
    K, n = 3, 1000
    rng = np.random.default_rng(0)
    gbar = rng.standard_normal((K, n)).astype(np.float32)
    msgs = code.B.astype(np.float32) @ gbar
    alive = np.array([True, True, False])
    a = code.decode_vector(alive).astype(np.float32)
    x = rng.standard_normal(n).astype(np.float32)
    y = rng.standard_normal(n).astype(np.float32)
    z = rng.standard_normal(n).astype(np.float32)
    tau, rho = 1.7, 0.8
    G = (a @ msgs) / K  # eq. (6) with decode
    expect = (tau * x + rho * z + y - G) / (rho + tau)
    out = coded_admm_update(
        jnp.asarray(msgs), jnp.asarray(a / K), jnp.asarray(x), jnp.asarray(y),
        jnp.asarray(z), jnp.asarray(tau), rho,
    )
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------


@pytest.mark.parametrize(
    "B,S,H,KV,hd,window",
    [
        (1, 256, 4, 4, 64, None),  # MHA causal
        (2, 256, 4, 2, 64, None),  # GQA
        (1, 512, 8, 1, 64, None),  # MQA
        (1, 512, 4, 2, 64, 128),  # sliding window
        (1, 384, 2, 2, 128, 100),  # non-pow2 window, hd=128
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, S, H, KV, hd, window, dtype):
    ks = jax.random.split(jax.random.key(S + H), 3)
    q = _rand(ks[0], (B, S, H, hd), dtype)
    k = _rand(ks[1], (B, S, KV, hd), dtype)
    v = _rand(ks[2], (B, S, KV, hd), dtype)
    out = flash_attention(q, k, v, causal=True, window=window)
    ref = flash_attention_ref(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        causal=True,
        window=window,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **TOL[dtype]
    )


def test_flash_attention_matches_model_layer():
    """Kernel == the models' blocked_attention (pre-expanded GQA) path."""
    from repro.models.layers import blocked_attention, _expand_kv

    B, S, H, KV, hd = 1, 512, 4, 2, 64
    ks = jax.random.split(jax.random.key(0), 3)
    q = _rand(ks[0], (B, S, H, hd), jnp.float32)
    k = _rand(ks[1], (B, S, KV, hd), jnp.float32)
    v = _rand(ks[2], (B, S, KV, hd), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=200)
    ref = blocked_attention(
        q, _expand_kv(k, H // KV), _expand_kv(v, H // KV),
        causal=True, window=200, block_q=128, block_kv=128,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


# --------------------------------------------------------------------------
# ssd_scan
# --------------------------------------------------------------------------


@pytest.mark.parametrize(
    "B,S,H,P,N,chunk",
    [
        (1, 128, 2, 16, 32, 64),
        (2, 256, 4, 32, 64, 128),
        (1, 200, 2, 16, 32, 64),  # padded path (S not chunk multiple)
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan(B, S, H, P, N, chunk, dtype):
    ks = jax.random.split(jax.random.key(S * H), 4)
    x = _rand(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(_rand(ks[1], (B, S, H), jnp.float32))
    A = -jnp.exp(_rand(ks[2], (H,), jnp.float32))
    Bm = _rand(ks[3], (B, S, N), dtype) / np.sqrt(N)
    Cm = _rand(ks[0], (B, S, N), dtype) / np.sqrt(N)
    y, h = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    y_ref, h_ref = ssd_scan_ref(x, dt, A, Bm, Cm)
    tol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), **tol)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), **tol)


def test_ssd_scan_matches_model_chunked():
    """Kernel == the mamba2 model's lax.scan ssd_chunked implementation."""
    from repro.models.mamba2 import ssd_chunked

    B, S, H, P, N = 1, 256, 2, 16, 32
    ks = jax.random.split(jax.random.key(1), 4)
    x = _rand(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(_rand(ks[1], (B, S, H), jnp.float32))
    A = -jnp.exp(_rand(ks[2], (H,), jnp.float32))
    Bm = _rand(ks[3], (B, S, N), jnp.float32) / np.sqrt(N)
    Cm = _rand(ks[0], (B, S, N), jnp.float32) / np.sqrt(N)
    y_k, h_k = ssd_scan(x, dt, A, Bm, Cm, chunk=64)
    y_m, h_m = ssd_chunked(x, dt, A, Bm, Cm, chunk=64)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_m), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_m), rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------
# rglru_scan
# --------------------------------------------------------------------------


@pytest.mark.parametrize(
    "B,S,W,block_s,block_w",
    [
        (1, 256, 64, 128, 64),
        (2, 512, 128, 256, 64),  # channel tiling (W > block_w)
        (1, 96, 32, 256, 512),  # block_s > S fallback
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_scan(B, S, W, block_s, block_w, dtype):
    ks = jax.random.split(jax.random.key(S + W), 3)
    a = jax.nn.sigmoid(_rand(ks[0], (B, S, W), jnp.float32)).astype(dtype)
    b = _rand(ks[1], (B, S, W), dtype)
    h, hlast = rglru_scan(a, b, block_s=block_s, block_w=block_w)
    h_ref, hlast_ref = rglru_scan_ref(a, b)
    tol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), **tol)
    np.testing.assert_allclose(np.asarray(hlast), np.asarray(hlast_ref), **tol)


def test_rglru_scan_initial_state():
    B, S, W = 2, 128, 32
    ks = jax.random.split(jax.random.key(9), 3)
    a = jax.nn.sigmoid(_rand(ks[0], (B, S, W), jnp.float32))
    b = _rand(ks[1], (B, S, W), jnp.float32)
    h0 = _rand(ks[2], (B, W), jnp.float32)
    h, hlast = rglru_scan(a, b, h0, block_s=64)
    h_ref, hlast_ref = rglru_scan_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(hlast), np.asarray(hlast_ref), rtol=1e-5, atol=1e-5)


def test_rglru_scan_matches_model():
    """Kernel == the rglru model's associative_scan path (given same gates)."""
    from repro.models.rglru import rglru_seq

    B, S, W = 1, 128, 32
    lp = {
        "lru_wa": jnp.eye(W) * 0.1,
        "lru_ba": jnp.full((W,), 1.0),
        "lru_wx": jnp.eye(W) * 0.1,
        "lru_bx": jnp.zeros((W,)),
        "lambda": jnp.full((W,), 1.0),
    }
    x = _rand(jax.random.key(3), (B, S, W), jnp.float32)
    h0 = jnp.zeros((B, W), jnp.float32)
    ys, hl = rglru_seq(lp, x, h0)
    # reproduce gates exactly as the model computes them
    from repro.models.rglru import _gates

    a, b = _gates(lp, x)
    h, hlast = rglru_scan(a, b, block_s=64)
    np.testing.assert_allclose(np.asarray(h), np.asarray(ys, np.float32), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(hlast), np.asarray(hl), rtol=1e-5, atol=1e-5)


def test_flash_attention_q_offset_continuation():
    """q_offset positions a query block mid-sequence (chunked prefill):
    attending over a longer KV prefix must equal the tail of full attention."""
    B, S, H, hd = 1, 512, 2, 64
    ks = jax.random.split(jax.random.key(11), 3)
    q = _rand(ks[0], (B, S, H, hd), jnp.float32)
    k = _rand(ks[1], (B, S, H, hd), jnp.float32)
    v = _rand(ks[2], (B, S, H, hd), jnp.float32)
    full = flash_attention(q, k, v, causal=True)
    half = flash_attention(
        q[:, S // 2 :], k, v, causal=True, q_offset=S // 2
    )
    np.testing.assert_allclose(
        np.asarray(half), np.asarray(full[:, S // 2 :]), rtol=2e-5, atol=2e-5
    )
