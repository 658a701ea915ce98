"""Code-family subsystem certification suite (DESIGN.md §11).

Every registered `CodeFamily` is swept over a (K, S) grid and held to the
contract the decode path relies on:

- any alive set of exactly R = K - S responses decodes the exact
  partition-gradient sum (or lands within the certified ``err_bound``
  for the partial-recovery family);
- decode vectors satisfy a^T B ~= 1^T (the all-ones target lies in the
  rowspan of the alive rows) and are supported on alive ECNs only;
- replication/storage accounting matches ``support()`` row by row;
- `make_code` rejects infeasible (K, S) with a clear, uniform
  ValueError *before* any construction math can fail cryptically
  (satellite regression tests pin the messages).

Deterministic tests run everywhere; the Hypothesis property section
(mirroring ``tests/test_coding_properties.py``) is defined only when
``hypothesis`` is installed (optional dev dependency, present in CI).
"""

import itertools

import numpy as np
import pytest

from repro.core.coding import (
    CODE_FAMILIES,
    CodeFamily,
    GradientCode,
    make_code,
    register_family,
)

try:
    from hypothesis import assume, given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # optional dev dependency; CI installs it
    HAVE_HYPOTHESIS = False

# The certification grid: every (family, K, S) that is feasible is built
# and certified; infeasible combos must raise the family's clear error.
KS_GRID = [(3, 1), (4, 1), (4, 2), (6, 1), (6, 2), (8, 3), (9, 2)]
FAMILIES = sorted(CODE_FAMILIES)


def _feasible(name: str, K: int, S: int) -> bool:
    if name == "uncoded":
        return S == 0
    try:
        CODE_FAMILIES[name].check(K, S)
    except ValueError:
        return False
    return True


def _grid(name: str):
    ks = [(K, 0) for K, _ in KS_GRID] if name == "uncoded" else KS_GRID
    return [(K, S) for K, S in dict.fromkeys(ks) if _feasible(name, K, S)]


def _alive_patterns(K: int, n_alive: int):
    for alive_idx in itertools.combinations(range(K), n_alive):
        alive = np.zeros(K, dtype=bool)
        alive[list(alive_idx)] = True
        yield alive


def _check_decode_contract(code: GradientCode, alive: np.ndarray, rng):
    """One alive pattern: decode identity, support, and error bound."""
    g = rng.standard_normal((code.K, 5))
    a = code.decode_vector(alive)
    # decode vector supported on alive ECNs only
    assert np.all(np.abs(a[~alive]) < 1e-12)
    resid = a @ code.B - np.ones(code.K)
    got = code.decode(code.encode(g), alive)
    err = np.abs(got - g.sum(0)).max()
    if code.exact:
        # a^T B == 1^T exactly: 1 lies in rowspan(B[alive])
        np.testing.assert_allclose(resid, 0, atol=1e-7)
        assert err < 1e-7
    else:
        # within the certified bound, per coordinate (Cauchy-Schwarz)
        bound = np.linalg.norm(resid)
        assert bound <= code.err_bound * (1 + 1e-6) + 1e-9
        col_norms = np.linalg.norm(g, axis=0)
        assert (np.abs(got - g.sum(0)) <= bound * col_norms + 1e-9).all()


@pytest.mark.parametrize("name", FAMILIES)
def test_family_certifies_across_grid(name):
    """verify() passes for every feasible (K, S) of every family."""
    grid = _grid(name)
    assert grid, f"{name}: empty feasible grid"
    for K, S in grid:
        code = make_code(name, K, S, seed=0)
        assert code.name == name and (code.K, code.S) == (K, S)
        assert code.verify(), f"{name} ({K},{S}) failed certification"


@pytest.mark.parametrize("name", FAMILIES)
def test_any_R_subset_decodes_within_contract(name):
    """Exhaustive over R-subsets: exact decode, or certified-bounded for
    the partial-recovery family."""
    rng = np.random.default_rng(0)
    for K, S in _grid(name):
        code = make_code(name, K, S, seed=1)
        for alive in _alive_patterns(K, code.R):
            _check_decode_contract(code, alive, rng)


def test_partial_recovery_below_R():
    """The approx family decodes from r_min <= r < R responses within the
    certified bound; exact families refuse the same patterns."""
    rng = np.random.default_rng(2)
    for K, S in [(4, 1), (6, 2), (8, 3)]:
        code = make_code("approx", K, S, seed=0)
        exact = make_code("cyclic", K, S, seed=0)
        assert code.min_responses < code.R and code.err_bound > 0
        for r in range(code.min_responses, code.R):
            for alive in itertools.islice(_alive_patterns(K, r), 12):
                _check_decode_contract(code, alive, rng)
                with pytest.raises(ValueError, match="responses"):
                    exact.decode_vector(alive)
        # residual is non-increasing in the alive set: the r_min bound
        # certifies every accepted pattern
        worst = max(
            code.decode_error(a)
            for a in _alive_patterns(K, code.min_responses)
        )
        assert worst <= code.err_bound * (1 + 1e-6) + 1e-9


def test_exact_families_flag_and_bound():
    for name in FAMILIES:
        fam = CODE_FAMILIES[name]
        K, S = _grid(name)[-1]
        code = make_code(name, K, S, seed=0)
        assert fam.exact == code.exact
        assert (code.err_bound == 0.0) == fam.exact


@pytest.mark.parametrize("name", FAMILIES)
def test_replication_matches_support(name):
    """Storage accounting: replication == max row support; repetition
    families store S+1 partitions per ECN, MDS stores all K."""
    for K, S in _grid(name):
        code = make_code(name, K, S, seed=0)
        sizes = [len(code.support(j)) for j in range(K)]
        assert code.replication == max(sizes)
        if name in ("fractional", "cyclic", "approx"):
            assert sizes == [S + 1] * K
        elif name == "mds":
            assert code.replication == K
        elif name == "uncoded":
            assert sizes == [1] * K


def test_mds_decodes_any_superset_of_R():
    """MDS flexibility: ANY >= R alive rows decode exactly (not just the
    fastest-R patterns repetition schemes certify)."""
    code = make_code("mds", 6, 2, seed=0)
    rng = np.random.default_rng(3)
    for n_alive in range(code.R, 7):
        for alive in _alive_patterns(6, n_alive):
            _check_decode_contract(code, alive, rng)


# -------------------------------------------------------------------------
# Stacked decode parity: decode_vectors against the per-pattern lstsq path
# -------------------------------------------------------------------------


def _lstsq_decode_vector(code: GradientCode, alive: np.ndarray) -> np.ndarray:
    """Frozen copy of the per-pattern decode that `decode_vectors`
    replaced: one ``np.linalg.lstsq`` per alive mask."""
    alive = np.asarray(alive, dtype=bool)
    if alive.sum() < code.min_responses:
        raise ValueError(
            f"need >= r_min={code.min_responses} responses, "
            f"got {int(alive.sum())}"
        )
    idx = np.nonzero(alive)[0]
    ones = np.ones(code.K)
    a_idx, *_ = np.linalg.lstsq(code.B[idx].T, ones, rcond=None)
    resid = np.linalg.norm(code.B[idx].T @ a_idx - ones)
    if resid > code._decode_tol():
        raise ValueError(
            f"alive set {idx.tolist()} is not decodable "
            f"(residual {resid:.3g} > certified {code._decode_tol():.3g})"
        )
    a = np.zeros(code.K)
    a[idx] = a_idx
    return a


def _lstsq_cond(code: GradientCode, alive: np.ndarray) -> float:
    """Condition number of the solve lstsq makes: largest over smallest
    singular value of ``B[alive]^T`` that its cutoff keeps."""
    s = np.linalg.svd(code.B[alive].T, compute_uv=False)
    s = s[s > np.finfo(np.float64).eps * code.K * s[0]]
    return float(s[0] / s[-1])


SMALL_CODES = [
    (name, K, S)
    for name in FAMILIES
    for K in range(1, 9)
    for S in range(K)
    if _feasible(name, K, S)
]


@pytest.mark.parametrize(
    "name,K,S", SMALL_CODES, ids=[f"{n}-K{K}-S{S}" for n, K, S in SMALL_CODES]
)
def test_stacked_decode_matches_per_pattern_lstsq(name, K, S):
    """Every alive mask of every small code: `decode_vectors` accepts
    exactly the patterns the per-pattern lstsq accepts, with the same
    vectors, and `decode_vector` raises lstsq's messages on the rest.

    A min-norm solve is fixed only to about cond * eps, so lstsq's own
    vector moves that much under rounding; the vectors must agree within
    1e-12 * cond, the pattern's condition number (below 100 on most
    patterns; the S = K - 1 cyclic draws are numerically rank one, cond
    ~ 1e14, where both solves keep a rounding direction).
    """
    code = make_code(name, K, S, seed=0)
    masks = ((np.arange(2**K)[:, None] >> np.arange(K)) & 1).astype(bool)
    vecs, ok = code.decode_vectors(masks)
    assert vecs.shape == (2**K, K) and ok.shape == (2**K,)
    for alive, vec, accepted in zip(masks, vecs, ok):
        try:
            want = _lstsq_decode_vector(code, alive)
        except ValueError as err:
            assert not accepted, alive
            assert (vec == 0).all()
            with pytest.raises(ValueError) as got:
                code.decode_vector(alive)
            assert str(got.value) == str(err)
            continue
        assert accepted, alive
        assert (vec[~alive] == 0).all()
        tol = 1e-12 * max(1.0, _lstsq_cond(code, alive))
        np.testing.assert_allclose(vec, want, rtol=0, atol=tol)
        np.testing.assert_array_equal(code.decode_vector(alive), vec)


# -------------------------------------------------------------------------
# make_code feasibility errors (satellite: clear messages, regression)
# -------------------------------------------------------------------------


def test_make_code_unknown_family_lists_known():
    with pytest.raises(ValueError, match="unknown code family 'nope'"):
        make_code("nope", 4, 1)
    with pytest.raises(ValueError, match="approx.*cyclic.*fractional"):
        make_code("reed-solomon", 4, 1)


@pytest.mark.parametrize(
    "scheme,K,S,msg",
    [
        ("fractional", 5, 1, r"'fractional' code infeasible for K=5, S=1: "
         r"needs \(S\+1\) \| K, but 2 does not divide 5"),
        ("fractional", 9, 1, r"needs \(S\+1\) \| K"),
        ("cyclic", 3, 5, r"'cyclic' code infeasible: need 0 <= S < K "
         r"\(got K=3, S=5\)"),
        ("cyclic", 4, -1, r"need 0 <= S < K"),
        ("mds", 4, 4, r"'mds' code infeasible: need 0 <= S < K"),
        ("approx", 6, 0, r"'approx' code infeasible for K=6, S=0: "
         r"partial recovery needs S >= 1"),
        ("uncoded", 4, 1, r"'uncoded' code infeasible for K=4, S=1: "
         r"uncoded tolerates no stragglers"),
    ],
)
def test_make_code_infeasible_messages(scheme, K, S, msg):
    """The regression contract: infeasible (K, S) surfaces as the
    family's uniform ValueError, never a cryptic construction failure."""
    with pytest.raises(ValueError, match=msg):
        make_code(scheme, K, S)


def test_arm_set_rejects_infeasible_arms_at_construction():
    """The controller regression contract (DESIGN.md §15): an infeasible
    (family, S, deadline) arm fails AT ARM-SET CONSTRUCTION with the
    uniform make_code message — never at trace time — and the whole set
    is pre-checked before any code is built."""
    from repro.core.coding import check_arm_set, make_arm_set

    good = ("cyclic", 1, None)
    with pytest.raises(
        ValueError,
        match=r"'approx' code infeasible for K=6, S=0: "
        r"partial recovery needs S >= 1",
    ):
        make_arm_set((good, ("approx", 0, 3e-4)), K=6)
    with pytest.raises(
        ValueError,
        match=r"'fractional' code infeasible for K=5, S=1: "
        r"needs \(S\+1\) \| K",
    ):
        check_arm_set((good, ("fractional", 1, None)), K=5)
    with pytest.raises(ValueError, match=r"'mds' code infeasible"):
        check_arm_set((good, ("mds", 6, None)), K=6)
    with pytest.raises(ValueError, match="unknown code family 'bogus'"):
        check_arm_set((good, ("bogus", 1, None)), K=6)
    with pytest.raises(ValueError, match="arm set is empty"):
        check_arm_set((), K=6)
    with pytest.raises(ValueError, match="duplicate arm"):
        check_arm_set((good, ("cyclic", 1, None)), K=6)
    with pytest.raises(ValueError, match="deadline must be positive"):
        check_arm_set((good, ("approx", 1, -1.0)), K=6)
    with pytest.raises(ValueError, match="not a \\(scheme, S, deadline\\)"):
        check_arm_set((("cyclic", 1),), K=6)
    # The happy path builds one certified code per arm, in arm order.
    codes = make_arm_set((good, ("approx", 2, 1e-3), ("mds", 1, None)), K=6)
    assert [c.name for c in codes] == ["cyclic", "approx", "mds"]
    assert all(c.verify() for c in codes)


def test_direct_builders_share_the_uniform_range_message():
    """Direct construction and the make_code registry path raise the
    SAME 'code infeasible' message for an out-of-range (K, S)."""
    from repro.core.coding import cyclic_repetition_code, mds_code

    msg = r"'cyclic' code infeasible: need 0 <= S < K \(got K=3, S=5\)"
    with pytest.raises(ValueError, match=msg):
        cyclic_repetition_code(3, 5)
    with pytest.raises(ValueError, match=msg):
        make_code("cyclic", 3, 5)
    with pytest.raises(ValueError, match=r"'mds' code infeasible"):
        mds_code(4, 4)


def test_register_family_rejects_duplicates():
    with pytest.raises(ValueError, match="duplicate code family"):
        register_family(CODE_FAMILIES["cyclic"])


def test_registry_contents():
    assert set(CODE_FAMILIES) == {
        "uncoded", "fractional", "cyclic", "mds", "approx"
    }
    for fam in CODE_FAMILIES.values():
        assert isinstance(fam, CodeFamily)


# -------------------------------------------------------------------------
# Hypothesis property section (skipped entirely when hypothesis absent,
# mirroring tests/test_coding_properties.py)
# -------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    @settings(max_examples=30, deadline=None)
    @given(
        name=st.sampled_from(FAMILIES),
        K=st.integers(3, 8),
        S=st.integers(0, 3),
        seed=st.integers(0, 2**16),
    )
    def test_property_any_R_subset_decode(name, K, S, seed):
        """Property: any feasible (family, K, S, seed) build certifies,
        and a random R-subset decodes within the family's contract."""
        if name == "uncoded":
            S = 0
        assume(_feasible(name, K, S))
        code = make_code(name, K, S, seed=seed)
        rng = np.random.default_rng(seed)
        alive = np.zeros(K, dtype=bool)
        alive[rng.choice(K, size=code.R, replace=False)] = True
        _check_decode_contract(code, alive, rng)

    @settings(max_examples=20, deadline=None)
    @given(
        K=st.integers(3, 8),
        S=st.integers(1, 3),
        seed=st.integers(0, 1000),
        data=st.data(),
    )
    def test_property_partial_recovery_bounded(K, S, seed, data):
        """Property: approx decode from any accepted sub-R pattern stays
        within the certified bound and in-support."""
        assume(S < K)
        code = make_code("approx", K, S, seed=seed)
        r = data.draw(
            st.integers(code.min_responses, code.K), label="n_alive"
        )
        rng = np.random.default_rng(seed)
        alive = np.zeros(K, dtype=bool)
        alive[rng.choice(K, size=r, replace=False)] = True
        _check_decode_contract(code, alive, rng)

    @settings(max_examples=20, deadline=None)
    @given(
        name=st.sampled_from(["fractional", "cyclic", "mds", "approx"]),
        K=st.integers(3, 8),
        S=st.integers(0, 3),
    )
    def test_property_replication_accounting(name, K, S):
        """Property: replication always equals the max support size, and
        storage never exceeds K partitions per ECN."""
        assume(_feasible(name, K, S))
        code = make_code(name, K, S, seed=0)
        sizes = [len(code.support(j)) for j in range(K)]
        assert code.replication == max(sizes) <= K
