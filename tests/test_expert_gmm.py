"""The grouped expert product (`repro.kernels.expert_gmm`, interpret mode
on the CPU) against its jnp twin (`repro.kernels.ref.expert_gmm_ref`):
forward and VJP with uneven and empty groups, several row, k and n tiles,
a worst-case buffer whose tail carries no rows, and one call per entry
under vmap. The twin and the kernel accumulate the same products in
float32 over different tilings, hence the float32 tolerance."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import expert_gmm
from repro.kernels.expert_gmm import pick_tiles
from repro.kernels.ref import expert_gmm_ref

TOL = dict(rtol=1e-5, atol=1e-4)

# (m, k, n, group sizes): sizes sum to at most m; rows past the sum are
# the buffer's empty tail.
CASES = {
    "uneven_with_empty": (64, 64, 48, [10, 0, 30, 20]),
    "all_empty": (48, 32, 32, [0, 0, 0]),
    "one_group_holds_all": (96, 32, 40, [0, 96, 0]),
    "row_and_k_tiles_tail": (1100, 2048, 128, [300, 0, 500, 200]),
    "n_tiles": (80, 64, 2048, [5, 70, 5]),
}


def _args(m, k, n, sizes, seed=0):
    rng = np.random.default_rng(seed)
    lhs = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((len(sizes), k, n)), jnp.float32)
    return lhs, rhs, jnp.asarray(sizes, jnp.int32)


def test_tiles_follow_the_moonlight_widths():
    # 512-row tiles; the expert width 1,408 is whole, d_model 2,048 in 512s.
    assert pick_tiles(49152, 2048, 1408) == (512, 512, 1408)
    assert pick_tiles(49152, 1408, 2048) == (512, 1408, 512)
    assert pick_tiles(40, 64, 48) == (48, 64, 48)


@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_matches_twin(case):
    lhs, rhs, gs = _args(*CASES[case])
    out = expert_gmm(lhs, rhs, gs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expert_gmm_ref(lhs, rhs, gs)), **TOL)
    # The empty tail of the buffer reads 0.
    assert not np.asarray(out)[int(gs.sum()):].any()


@pytest.mark.parametrize("case", sorted(CASES))
def test_vjp_matches_twin(case):
    lhs, rhs, gs = _args(*CASES[case])
    cot = jax.random.normal(jax.random.key(1), (lhs.shape[0], rhs.shape[2]), jnp.float32)

    def loss(fn):
        return lambda a, b: jnp.sum(fn(a, b, gs) * cot)

    got = jax.grad(loss(expert_gmm), (0, 1))(lhs, rhs)
    want = jax.grad(loss(expert_gmm_ref), (0, 1))(lhs, rhs)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), **TOL)
    # No gradient reaches the tail rows, and an empty group's weights get 0.
    assert not np.asarray(got[0])[int(gs.sum()):].any()
    for e in np.flatnonzero(np.asarray(gs) == 0):
        assert not np.asarray(got[1][e]).any()


def test_one_call_per_entry_under_vmap():
    lhs, rhs, gs = _args(*CASES["uneven_with_empty"])
    lb = jnp.stack([lhs, 2 * lhs])
    gb = jnp.stack([gs, gs[::-1]])
    out = jax.vmap(expert_gmm, in_axes=(0, None, 0))(lb, rhs, gb)
    want = jax.vmap(expert_gmm_ref, in_axes=(0, None, 0))(lb, rhs, gb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **TOL)

    def loss(fn):
        return lambda a, b, g: jnp.sum(fn(a, b, g) ** 2)

    got = jax.vmap(jax.grad(loss(expert_gmm), (0, 1)), in_axes=(0, None, 0))(lb, rhs, gb)
    want = jax.vmap(jax.grad(loss(expert_gmm_ref), (0, 1)), in_axes=(0, None, 0))(lb, rhs, gb)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5, atol=1e-3)


def test_bfloat16_rows_accumulate_in_float32():
    lhs, rhs, gs = _args(*CASES["uneven_with_empty"])
    lhs, rhs = lhs.astype(jnp.bfloat16), rhs.astype(jnp.bfloat16)
    out = expert_gmm(lhs, rhs, gs)
    assert out.dtype == jnp.bfloat16
    # Both round one float32 sum of the same products to bfloat16.
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expert_gmm_ref(lhs, rhs, gs), np.float32),
        rtol=1e-2, atol=1e-2,
    )
