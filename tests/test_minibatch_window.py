"""The step's mini-batch fetch: K windows of MU rows, exactly the rows of a
per-row index.

`repro.methods.admm._minibatch` fetches agent i's K partitions'
sub-batches as K windows of MU consecutive rows of the flat (N*b, ...)
pool, one start index per window. The rows it must give are those of the
per-row index i*b + k*P + off + r, r < MU, each clamped on its own into
the pool: a plain numpy fancy index. The cases below reach the pool's
end: in a group of mixed S (mu < MU) the last agent's last window runs
up to MU - mu rows past the data, and with the adaptive kernel each arm
has its own mu and its own offsets.
"""

import jax
import numpy as np
import pytest

from repro.control import ADAPTIVE_KERNEL
from repro.core.graph import make_network
from repro.core.problems import DATASETS, allocate
from repro.experiments import Case
from repro.methods import admm, get_kernel
from repro.methods.base import TableRow

# usps stand-in, N = 5 agents of b = 198 rows, K = 6 partitions of P = 33.
N, K, M = 5, 6, 36


def _problem(seed=0):
    return allocate(DATASETS["usps"](seed), N, K)


def _expected(pool, i, off, P, MU):
    """The per-row index, each row clamped into the pool on its own."""
    b = pool.shape[0] // N
    idx = i * b + np.arange(K)[:, None] * P + off + np.arange(MU)[None, :]
    return pool[np.minimum(idx, len(pool) - 1)], idx


def _fetch_fn(kernel, statics):
    @jax.jit
    def fetch(consts, i, off):
        aux = kernel.setup(consts, statics)
        return admm._minibatch(aux, i, off, statics)

    return fetch


def _prepared(kernel, case, prob):
    net = make_network(case.N, case.connectivity, seed=case.seed)
    return kernel.prepare(prob, net, kernel.config(case), case.iters)


@pytest.mark.parametrize("as_table", [False, True], ids=["own", "table_row"])
@pytest.mark.parametrize("S", [1, 2])
def test_windows_are_the_clamped_rows_of_a_mixed_S_group(S, as_table):
    """mu = 3 or 2 under the group's MU = 6 (an S = 0 run's): at every
    agent and offset the windows hold the rows of the clamped per-row
    index, the last agent's last ones past the end of the data too. The
    run reads its own arrays (serial, unshared batch) or its row of a
    shared table."""
    prob = _problem()
    kernel = get_kernel("csI-ADMM")
    case = Case(method="csI-ADMM", dataset="usps", N=N, K=K, M=M,
                scheme="cyclic", S=S, iters=10)
    prep = _prepared(kernel, case, prob)
    mu = int(prep.max_statics["MU"])
    MU = M // K  # the bound of the group's S = 0 runs
    assert mu < MU
    statics = dict(prep.statics, MU=MU)
    P = statics["P"]
    consts = tuple(prep.consts)
    if as_table:
        other = _problem(seed=1)
        consts = (
            TableRow(np.stack([other.O, prob.O]), np.int32(1)),
            TableRow(np.stack([other.T, prob.T]), np.int32(1)),
        ) + consts[2:]
    fetch = _fetch_fn(kernel, statics)
    O_pool = prob.O.reshape(-1, prob.p)
    T_pool = prob.T.reshape(-1, prob.d)
    past_end = 0
    for i in range(N):
        for off in range(0, P - mu + 1):
            Ob, Tb = fetch(consts, np.int32(i), np.int32(off))
            want_O, idx = _expected(O_pool, i, off, P, MU)
            want_T, _ = _expected(T_pool, i, off, P, MU)
            np.testing.assert_array_equal(np.asarray(Ob), want_O)
            np.testing.assert_array_equal(np.asarray(Tb), want_T)
            past_end += int((idx >= len(O_pool)).sum())
    assert past_end > 0  # the last agent's last windows ran past the data


def test_windows_under_the_adaptive_kernels_per_arm_mu():
    """a-csI-ADMM: each arm has its own mu and offsets, and the step
    re-masks the rows per arm. Under a group MU above every arm's mu, the
    fetched rows weighted by each arm's mask are the weighted rows of the
    clamped per-row index, at every step of every arm."""
    prob = _problem()
    case = Case(method="a-csI-ADMM", dataset="usps", N=N, K=K, M=M,
                arms=(("cyclic", 1, None), ("cyclic", 2, None)), iters=80)
    prep = _prepared(ADAPTIVE_KERNEL, case, prob)
    MU = M // K  # above both arms' mu (3 and 2)
    statics = dict(prep.statics, MU=MU)
    P = statics["P"]
    mu_arms = prep.consts[7]
    assert sorted(mu_arms.tolist()) == [2, 3]
    agents, offsets = prep.steps[0], prep.steps[1]  # offsets: (iters, arms)
    fetch = _fetch_fn(ADAPTIVE_KERNEL, statics)
    O_pool = prob.O.reshape(-1, prob.p)
    T_pool = prob.T.reshape(-1, prob.d)
    past_end = 0
    seen = set()
    for k in range(len(agents)):
        for a, mu in enumerate(mu_arms):
            i, off = int(agents[k]), int(offsets[k, a])
            if (i, off) in seen:
                continue
            seen.add((i, off))
            Ob, Tb = fetch(tuple(prep.consts), np.int32(i), np.int32(off))
            valid = (np.arange(MU) < mu)[None, :, None]
            want_O, idx = _expected(O_pool, i, off, P, MU)
            want_T, _ = _expected(T_pool, i, off, P, MU)
            np.testing.assert_array_equal(np.asarray(Ob), want_O)
            np.testing.assert_array_equal(valid * np.asarray(Ob), valid * want_O)
            np.testing.assert_array_equal(valid * np.asarray(Tb), valid * want_T)
            past_end += int((idx >= len(O_pool)).sum())
    assert past_end > 0


@pytest.mark.parametrize("size", [6, 130])
def test_pool_reads_the_last_row_past_the_data(size):
    """Past the data the pool repeats its last row, as a clamped row index
    reads it, as far as a window of ``size`` rows reaches: the windows at
    the pool's end, one of more than a lane tile of rows too."""
    prob = _problem()
    flat = np.concatenate(
        [prob.O.reshape(-1, prob.p), prob.T.reshape(-1, prob.d)], axis=1
    )
    n = len(flat)
    pool = admm._pool(
        TableRow(prob.O[None], 0), TableRow(prob.T[None], 0), size
    )
    starts = np.array([0, n - size, n - 2, n - 1, n], dtype=np.int32)
    got = np.asarray(admm._windows(pool, starts, size))
    idx = np.minimum(starts[:, None] + np.arange(size)[None, :], n - 1)
    np.testing.assert_array_equal(got, flat[idx])
