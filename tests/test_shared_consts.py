"""Shared const tables of the batched engine (DESIGN.md §7, §9).

The grid points of one seed hold the same `LeastSquaresProblem`, so their
data arrays are the same host objects. The batched pipeline ships such a
const once (`driver._stack_consts`), as a row of a table, with an int32 index per run; the
executable takes each run's rows on the device. The results must be
those of per-run stacking: batched equals serial as the other tier tests
require, sharded equals batched bitwise, and equal (R, U) jobs share one
executable. conftest.py forces 8 CPU devices and float64.
"""

import copy
import dataclasses

import jax
import numpy as np
import pytest

from repro.experiments import Case
from repro.experiments.sweep import _materialize
from repro.methods import Reduction, driver, get_kernel, run_batch, run_serial
from repro.methods import run_sharded

ITERS = 30
TRACE_FIELDS = (
    "accuracy", "test_error", "z_err", "comm_cost", "sim_time",
    "final_x", "final_z",
)
SPEC = Reduction(
    fields=("accuracy", "test_error", "z_err"),
    budgets=(0.005, 0.05),
    x="sim_time",
    targets=(0.5, 0.2),
    quantiles=(0.1, 0.5, 0.9),
    final_x=True,
)
# Four configs of one seed: 2 straggler tolerances x 2 code families.
CONFIGS = [dict(S=S, scheme=sc) for S in (1, 2) for sc in ("cyclic", "mds")]
# Twelve configs of one seed: the four above x 3 penalties. With 2 seeds
# the tables ship 2 x 8 rows to the 8 devices against 24 per-run rows.
WIDE = [dict(c, rho=rho) for c in CONFIGS for rho in (1.0, 0.5, 2.0)]
# Positions of the ADMM kernel's consts that are the problem's arrays:
# O, T, O_test, T_test (x_star, rho and mu are new objects every run).
DATA = (True, True, False, True, True, False, False)


def _stacked(kernel, probs, nets, cfgs, clock=False):
    """The group's prepared runs, jit statics and stacked inputs on one
    device, as the batched pipeline builds them."""
    preps = [kernel.prepare(p, n, c, ITERS) for p, n, c in zip(probs, nets, cfgs)]
    bound = driver._statics_bound(kernel, probs, cfgs, ITERS)
    return preps, {**preps[0].statics, **bound}, driver._stack_runs(
        preps, clock, 1
    )


def _grid(seeds, configs):
    """Runs of ``seeds`` x ``configs``, materialized with the sweep's
    caches, so the configs of a seed hold one problem."""
    kernel = get_kernel("csI-ADMM")
    cases = [
        Case(method="csI-ADMM", dataset="usps", N=5, K=6, M=36,
             iters=ITERS, seed=s, **c)
        for s in seeds for c in configs
    ]
    nets, probs = {}, {}
    mats = [_materialize(c, nets, probs) for c in cases]
    return (
        kernel, [m[1] for m in mats], [m[0] for m in mats],
        [kernel.config(c) for c in cases],
    )


@pytest.mark.parametrize("reduced", [False, True], ids=["traces", "summaries"])
@pytest.mark.parametrize(
    "configs,shared,own_T",
    [
        (CONFIGS, DATA, False),
        (CONFIGS[:1], (False,) * 7, False),
        (CONFIGS, (True, False) + DATA[2:], True),
    ],
    ids=["tables", "one-run-per-problem", "T-per-run"],
)
def test_batched_equals_serial(configs, shared, own_T, reduced):
    """3 seeds: with 4 configs a seed, the data ships as 3-row tables;
    with one, every position stacks per run. With a copy of T per run, O
    ships as a table and T per run, so the kernel, which reads O and T
    by rows of their tables, takes each run's own. All equal the serial
    tier."""
    kernel, probs, nets, cfgs = _grid(range(3), configs)
    if own_T:
        probs = [dataclasses.replace(p, T=p.T.copy()) for p in probs]
    R = len(probs)
    _, _, batch = _stacked(kernel, probs, nets, cfgs)
    assert batch.shared == shared
    if any(shared):
        assert [len(t) for t in batch.tables] == [3] * sum(shared)
        np.testing.assert_array_equal(batch.index, np.repeat(range(3), 4))
    else:
        assert batch.tables == () and batch.index is None
        assert len(batch.per_run) == 2  # (consts, steps), as with no tables
    assert [len(c) for c in batch.consts] == [R] * (7 - sum(shared))

    got = run_batch(
        kernel, probs, nets, cfgs, ITERS, reductions=SPEC if reduced else None
    )
    for r, (p, n, c) in enumerate(zip(probs, nets, cfgs)):
        if reduced:
            ref = run_serial(kernel, p, n, c, ITERS, reductions=SPEC)
            pairs = [(k, got[k][r], ref[k]) for k in ref]
        else:
            ref = run_serial(kernel, p, n, c, ITERS)
            pairs = [
                (f, getattr(got[r], f), getattr(ref, f)) for f in TRACE_FIELDS
            ]
        for name, a, b in pairs:
            np.testing.assert_allclose(
                a, b, rtol=1e-5, atol=1e-5, err_msg=f"run {r} {name}"
            )


def test_tables_hold_each_runs_own_consts():
    """Row ``index[r]`` of every table is run r's const, bit for bit."""
    kernel, probs, nets, cfgs = _grid(range(3), CONFIGS)
    preps, _, batch = _stacked(kernel, probs, nets, cfgs)
    positions = [i for i, s in enumerate(batch.shared) if s]
    for r, pr in enumerate(preps):
        for table, i in zip(batch.tables, positions):
            np.testing.assert_array_equal(table[batch.index[r]], pr.consts[i])


NO_TABLE = (False, False, False)


@pytest.mark.parametrize(
    "first,second,copies,want_shared,want_index",
    [
        # Position 0 by pairs, position 1 one object for all: a row per
        # distinct combination, so both tables have 2 rows.
        ([0, 0, 1, 1], [0, 0, 0, 0], 1, (True, True, False), [0, 0, 1, 1]),
        # Rows follow first appearance.
        ([1, 0, 1, 0], [0, 0, 0, 0], 1, (True, True, False), [0, 1, 0, 1]),
        # Only position 1 is shared: a one-row table.
        ([0, 1, 2, 3], [0, 0, 0, 0], 1, (False, True, False), [0, 0, 0, 0]),
        # Pairs that cross make a row per run, which saves nothing: the
        # whole group stacks per run.
        ([0, 0, 1, 1], [0, 1, 0, 1], 1, NO_TABLE, None),
        # Nothing shared.
        ([0, 1, 2, 3], [0, 1, 2, 3], 1, NO_TABLE, None),
        # Replicated on 2 devices, 2 rows ship 4 times: no fewer than the
        # 4 per-run rows, so no table.
        ([0, 0, 1, 1], [0, 0, 0, 0], 2, NO_TABLE, None),
        # One row on 2 devices ships 2 rows against 4.
        ([0, 1, 2, 3], [0, 0, 0, 0], 2, (False, True, False), [0, 0, 0, 0]),
        # 3 runs pad to 4 on 4 devices: one row shipped 4 times saves
        # nothing.
        ([0, 1, 2], [0, 0, 0], 4, NO_TABLE, None),
    ],
    ids=["pairs", "first-appearance", "one-row", "crossed", "none",
         "pairs-on-2-devices", "one-row-on-2-devices", "one-row-on-4-devices"],
)
def test_stack_consts_groups_by_identity(first, second, copies, want_shared,
                                        want_index):
    data = [np.full((2, 3), float(g)) for g in range(4)]
    other = [np.arange(5.0) + g for g in range(4)]
    per_run = [
        # Equal values in new objects never share: identity decides.
        (data[g], other[h], np.asarray(float(g)))
        for g, h in zip(first, second)
    ]
    shared, tables, index, consts = driver._stack_consts(per_run, copies)
    assert shared == want_shared
    if want_index is None:
        assert index is None and tables == ()
    else:
        np.testing.assert_array_equal(index, want_index)
        assert index.dtype == np.int32
    own = [i for i, s in enumerate(shared) if not s]
    assert len(consts) == len(own)
    for r, run in enumerate(per_run):
        t, c = iter(tables), iter(consts)
        for i, s in enumerate(shared):
            got = next(t)[index[r]] if s else next(c)[r]
            np.testing.assert_array_equal(got, run[i])


def test_equal_jobs_reuse_one_executable():
    """Two jobs of equal R and U (fresh seeds, fresh problems) run on one
    compiled executable: the table layout is the only new static."""
    driver._batch_reduced_fn.cache_clear()
    for seeds in (range(3), range(3, 6)):
        run_batch(*_grid(seeds, CONFIGS), ITERS, reductions=SPEC)
    assert driver._batch_reduced_fn.cache_info().currsize == 1
    kernel, probs, nets, cfgs = _grid(range(3), CONFIGS)
    _, statics, batch = _stacked(kernel, probs, nets, cfgs, clock=True)
    fn = driver._batch_reduced_fn(
        kernel, driver._statics_key(statics), SPEC, batch.shared, 1, False
    )
    assert driver._batch_reduced_fn.cache_info().currsize == 1
    assert fn._cache_size() == 1


@pytest.mark.skipif(len(jax.devices()) != 8, reason="needs 8 devices")
@pytest.mark.parametrize(
    "seeds,configs,shared",
    [(2, WIDE, DATA), (3, CONFIGS, (False,) * 7)],
    ids=["tables", "per-run"],
)
def test_sharded_equals_batched_bitwise(monkeypatch, seeds, configs, shared):
    """The sharded tier replicates tables on the 8 devices only where
    that ships fewer rows than per-run stacking: 2 seeds x 12 configs
    ship 2 x 8 rows against 24, while 3 seeds x 4 configs would ship
    3 x 8 against 16 padded runs and stack per run. Either way the
    results are batched's, bit for bit."""
    kernel, probs, nets, cfgs = _grid(range(seeds), configs)
    seen = []
    stack = driver._stack_runs

    def spy(*a, **kw):
        out = stack(*a, **kw)
        seen.append(out.shared)
        return out

    monkeypatch.setattr(driver, "_stack_runs", spy)
    batched = run_batch(kernel, probs, nets, cfgs, ITERS)
    sharded = run_sharded(kernel, probs, nets, cfgs, ITERS)
    # run_batch ships its tables once: 2 or 3 rows against 24 or 12 runs.
    assert seen == [DATA, shared]
    for r, (tb, ts) in enumerate(zip(batched, sharded)):
        for f in TRACE_FIELDS:
            np.testing.assert_array_equal(
                getattr(tb, f), getattr(ts, f), err_msg=f"run {r} {f}"
            )


def _flat(out):
    """Every output number of a tier, by name: a dict of summaries or a
    list of Traces."""
    if isinstance(out, dict):
        return out
    return {f"{r} {f}": getattr(t, f) for r, t in enumerate(out)
            for f in TRACE_FIELDS}


@pytest.mark.parametrize(
    "tier",
    ["batched-traces", "batched-summaries", "sharded-chunks"],
)
def test_tables_change_no_bit(monkeypatch, tier):
    """The same grid with each run's problem copied (nothing shared, so
    every run ships its own data) gives the same bits. In the sharded
    tier a budget of 3 runs a device cuts 3 seeds x 12 configs into
    chunks of 24 and 12 runs, which ship 2 and 1 table rows."""
    if tier == "sharded-chunks":
        grid, run, kw = _grid(range(3), WIDE), run_sharded, {}
        kernel, probs, nets, cfgs = grid
        prep = kernel.prepare(probs[0], nets[0], cfgs[0], ITERS)
        budget = 2 * 3 * driver._run_bytes(prep, clock=False)
        monkeypatch.setattr(driver, "_MEM_BUDGET", budget)
    else:
        grid, run = _grid(range(3), CONFIGS), run_batch
        kw = {"reductions": SPEC} if tier.endswith("summaries") else {}
    kernel, probs, nets, cfgs = grid
    own = [copy.deepcopy(p) for p in probs]
    with_tables = _flat(run(kernel, probs, nets, cfgs, ITERS, **kw))
    per_run = _flat(run(kernel, own, nets, cfgs, ITERS, **kw))
    assert with_tables.keys() == per_run.keys()
    for k in with_tables:
        np.testing.assert_array_equal(with_tables[k], per_run[k], err_msg=k)

