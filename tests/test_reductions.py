"""Streaming in-scan reduction tests (DESIGN.md §12).

The layer's contract: a `Reduction` folded into the scan carry equals
the post-hoc numpy reduction of the materialized `Trace` (<= 1e-5) on
every execution tier, with sharded == batched BITWISE; chunked streaming
execution is invisible in the outputs; and the results plumbing
(`run_sweep`/`reduce_mean`/`emit_rows`) consumes pre-reduced grid arrays.
Satellite regressions ride along: the vectorized `resample_runs` must be
bit-identical to the per-run searchsorted loop, and integer-typed fields
must promote to float before CI math.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.core.admm import ADMMConfig, Trace
from repro.core.graph import make_network
from repro.core.problems import DATASETS, allocate
from repro.experiments import (
    Case,
    Reduction,
    SweepSpec,
    get_sweep,
    mean_ci,
    reduce_mean,
    reduce_trace,
    resample_runs,
    run_sweep,
)
from repro.methods import (
    KERNELS, driver, get_kernel, run_batch, run_serial, run_sharded,
)
from repro.methods.admm import ADMMRun

ITERS = 40

FULL_SPEC = Reduction(
    fields=("accuracy", "test_error", "z_err"),
    budgets=(0.005, 0.05, 0.2),
    x="sim_time",
    targets=(0.5, 0.2),
    quantiles=(0.1, 0.5, 0.9),
    final_x=True,
)


def _admm_runs(n=3):
    probs, nets, cfgs = [], [], []
    for s in range(n):
        S = (1, 2, 0)[s % 3]
        nets.append(make_network(5, 0.5, seed=s))
        probs.append(allocate(DATASETS["usps"](s), 5, 6))
        cfgs.append(
            ADMMRun(
                ADMMConfig(
                    M=36, K=6, S=S,
                    scheme="cyclic" if S else "uncoded", seed=s,
                )
            )
        )
    return probs, nets, cfgs


def test_spec_validation():
    with pytest.raises(ValueError, match="fields"):
        Reduction(fields=("bogus",))
    with pytest.raises(ValueError, match="fields"):
        Reduction(fields=())
    with pytest.raises(ValueError, match="axis"):
        Reduction(x="iterations")
    with pytest.raises(ValueError, match="budgets"):
        Reduction(budgets=(0.0,))
    with pytest.raises(ValueError, match="quantiles"):
        Reduction(quantiles=(1.5,))
    with pytest.raises(ValueError, match="hi > lo"):
        Reduction(quantiles=(0.5,), lo=1.0, hi=1.0)
    # hashable: specs are jit cache keys
    assert hash(FULL_SPEC) == hash(dataclasses.replace(FULL_SPEC))


def test_reduce_trace_semantics():
    """Unit semantics of the numpy reference on a hand-built trace."""
    tr = Trace(
        accuracy=np.array([0.9, 0.6, 0.3, 0.1]),
        test_error=np.array([4.0, 3.0, 2.0, 1.0]),
        comm_cost=np.array([1.0, 2.0, 3.0, 4.0]),
        sim_time=np.array([1.0, 2.0, 3.0, 4.0]),
        z_err=np.array([0.9, 0.6, 0.3, 0.1]),
        final_x=np.zeros((2, 2, 1)),
        final_z=np.zeros((2, 1)),
    )
    spec = Reduction(
        fields=("accuracy",), budgets=(0.5, 2.5, 9.0),
        targets=(0.65, 0.05), quantiles=(0.5,), bins=10, lo=0.0, hi=1.0,
    )
    out = tr.reduce(spec)
    assert out["sim_time/final"] == 4.0 and out["comm_cost/final"] == 4.0
    assert out["accuracy/final"] == 0.1
    np.testing.assert_allclose(out["accuracy/mean"], 0.475)
    np.testing.assert_allclose(
        out["accuracy/var"], np.var([0.9, 0.6, 0.3, 0.1], ddof=1)
    )
    assert out["accuracy/min"] == 0.1
    # budget 0.5 precedes the first completion -> hold-first; 2.5 -> the
    # 2nd iteration's value; 9.0 past the end -> final value.
    np.testing.assert_allclose(out["accuracy/at_budget"], [0.9, 0.6, 0.1])
    # first sim_time with accuracy <= 0.65 is iteration 2 (t=2.0);
    # 0.05 is never reached.
    np.testing.assert_allclose(out["accuracy/time_to"], [2.0, np.inf])
    # median of bins {9, 6, 3, 1} in a 10-bin [0,1) sketch: bin 3 center
    np.testing.assert_allclose(out["accuracy/quantiles"], [0.35])


@pytest.mark.parametrize("x", ["sim_time", "comm_cost"])
def test_serial_streaming_matches_reduce_trace(x):
    spec = dataclasses.replace(FULL_SPEC, x=x)
    kernel = get_kernel("csI-ADMM")
    probs, nets, cfgs = _admm_runs(2)
    for p, n, c in zip(probs, nets, cfgs):
        ref = reduce_trace(spec, run_serial(kernel, p, n, c, ITERS))
        got = run_serial(kernel, p, n, c, ITERS, reductions=spec)
        assert set(got) == set(ref) == set(spec.keys())
        for k in ref:
            np.testing.assert_allclose(
                got[k], ref[k], rtol=1e-5, atol=1e-5, err_msg=k
            )


@pytest.mark.parametrize(
    "method",
    ["W-ADMM", "D-ADMM", "DGD", "EXTRA", "pI-ADMM", "cq-sI-ADMM", "I-ADMM"],
)
def test_every_kernel_streams_correctly(method):
    """Deterministic cross-kernel parity (the hypothesis property test in
    test_reductions_properties.py fuzzes the spec too, when available):
    every registered kernel family's in-scan fold matches reduce_trace
    serially AND through the batched driver, on both cost axes."""
    kernel = get_kernel(method)
    coded = method in ("pI-ADMM", "cq-sI-ADMM")
    case = Case(
        method=method, dataset="usps", N=5, K=3, M=30, iters=30, seed=1,
        S=1 if coded else 0, scheme="cyclic" if coded else "uncoded",
    )
    net = make_network(case.N, 0.5, seed=1)
    prob = allocate(DATASETS["usps"](1), case.N, case.K)
    cfg = kernel.config(case)
    tr = run_serial(kernel, prob, net, cfg, case.iters)
    for x in ("sim_time", "comm_cost"):
        spec = dataclasses.replace(FULL_SPEC, x=x)
        ref = reduce_trace(spec, tr)
        got = run_serial(kernel, prob, net, cfg, case.iters, reductions=spec)
        gb = run_batch(
            kernel, [prob] * 2, [net] * 2, [cfg] * 2, case.iters,
            reductions=spec,
        )
        for k in ref:
            np.testing.assert_allclose(
                got[k], ref[k], rtol=1e-5, atol=1e-5, err_msg=f"{x} {k}"
            )
            np.testing.assert_allclose(
                gb[k][0], ref[k], rtol=1e-5, atol=1e-5,
                err_msg=f"batch {x} {k}",
            )


def test_batched_and_sharded_streaming_agree():
    """Streaming tier contract: sharded == batched to near machine
    precision, both match the serial streaming run to 1e-5 (DESIGN.md
    §12). Unlike the materialized path's stacked metrics, the in-scan
    fold fuses with the kernel math, and XLA's fusion choices vary with
    the per-device vmap batch size — so tier agreement is last-ulp
    close, not bitwise."""
    kernel = get_kernel("csI-ADMM")
    probs, nets, cfgs = _admm_runs(3)
    b = run_batch(kernel, probs, nets, cfgs, ITERS, reductions=FULL_SPEC)
    s = run_sharded(kernel, probs, nets, cfgs, ITERS, reductions=FULL_SPEC)
    for i in range(3):
        ref = run_serial(
            kernel, probs[i], nets[i], cfgs[i], ITERS, reductions=FULL_SPEC
        )
        for k in ref:
            np.testing.assert_allclose(
                b[k][i], s[k][i], rtol=1e-12, atol=1e-12,
                err_msg=f"run{i} {k}: sharded != batched",
            )
            np.testing.assert_allclose(
                b[k][i], ref[k], rtol=1e-5, atol=1e-5,
                err_msg=f"run{i} {k}",
            )


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs a device mesh")
def test_chunked_streaming_matches_unchunked(monkeypatch):
    """R > chunk: outputs must be invariant to the chunk boundaries (and
    to the pad-by-repeat of the ragged last chunk) — to last-ulp
    tolerance, since the chunks' per-device vmap batch sizes differ and
    fusion choices move with them."""
    kernel = get_kernel("csI-ADMM")
    D = len(jax.devices())
    probs, nets, cfgs = _admm_runs(D + 2)
    whole = run_sharded(
        kernel, probs, nets, cfgs, ITERS, reductions=FULL_SPEC
    )
    # A zero budget clamps every dispatch to D runs: 2 chunks here.
    monkeypatch.setattr(driver, "_MEM_BUDGET", 0)
    chunked = run_sharded(
        kernel, probs, nets, cfgs, ITERS, reductions=FULL_SPEC
    )
    for k in whole:
        np.testing.assert_allclose(
            whole[k], chunked[k], rtol=1e-12, atol=1e-12, err_msg=k
        )


ARMS = (("cyclic", 1, None), ("cyclic", 2, None), ("approx", 2, 1e-3))


@pytest.mark.parametrize("method", sorted(KERNELS))
def test_max_statics_bound_exact_for_admm(method):
    """The batched pipeline's one-statics rule: the jit statics come from
    the hook before any run is prepared, so for every registered kernel
    the hook equals the prepared ``max_statics`` for mixed-(M, S) runs
    (mu = M_bar // K, no sampling) — the adaptive kernel over more than
    one arm. Gossip and Walkman kernels have none, so the base default
    holds."""
    kernel = get_kernel(method)
    prob = allocate(DATASETS["usps"](0), 5, 3)
    net = make_network(5, 0.5, seed=0)
    arms = ARMS if method == "a-csI-ADMM" else ()
    # M divides by (S + 1) * K for every arm's S as well.
    for M, S, scheme in ((36, 0, "uncoded"), (36, 1, "cyclic"),
                         (72, 1, "cyclic")):
        case = Case(method=method, dataset="usps", N=5, K=3, M=M, S=S,
                    scheme=scheme, arms=arms)
        run = kernel.config(case)
        bound = kernel.max_statics_bound(prob, run, 10)
        prep = kernel.prepare(prob, net, run, 10)
        assert bound == prep.max_statics, (M, S)
    assert get_kernel("DGD").max_statics_bound(prob, None, 10) == {}


def test_sweep_streaming_all_tiers_match_materialized():
    """run_sweep(reductions=...) on the fig5-style grid equals reducing
    the materialized traces, for every execution tier."""
    spec = SweepSpec(
        "stream_smoke",
        Case(
            method="csI-ADMM", dataset="usps", N=5, K=6, M=36,
            scheme="cyclic", iters=ITERS,
        ),
        axes={"S": [0, 1, 2], "seed": [0, 1]},
        fixup=lambda c: dataclasses.replace(
            c, scheme="uncoded" if c.S == 0 else c.scheme
        ),
        reductions=FULL_SPEC,
    )
    mat = run_sweep(spec.cases(), mode="batched")
    refs = [reduce_trace(FULL_SPEC, t) for t in mat.traces]
    for mode in ("serial", "batched", "sharded"):
        res = run_sweep(spec, mode=mode)
        assert res.reduced is not None and res.traces == []
        assert res.n_dispatches == 1  # whole S x seed grid: one group
        assert set(res.reduced) == set(FULL_SPEC.keys())
        for k in res.reduced:
            assert res.reduced[k].shape[0] == len(res.cases)
            for i, ref in enumerate(refs):
                np.testing.assert_allclose(
                    res.reduced[k][i], ref[k], rtol=1e-5, atol=1e-5,
                    err_msg=f"{mode} case{i} {k}",
                )


def test_streamed_reduce_mean_and_emit_rows():
    from benchmarks.common import Rows

    from repro.experiments import emit_rows

    spec = get_sweep("fleet_frontier", iters=10, runs=2)
    res = run_sweep(spec, mode="batched")
    assert res.reduced is not None
    # plain metric name -> the "/final" readout; full keys work verbatim
    red = reduce_mean(res, by=("scheme", "S"), field="accuracy")
    assert all(r["n"] == 4 and r["mean"].shape == () for r in red.values())
    red_b = reduce_mean(res, by=("scheme",), field="accuracy/at_budget")
    assert all(r["mean"].shape == (4,) for r in red_b.values())
    with pytest.raises(KeyError, match="not in the streamed reduction"):
        reduce_mean(res, by=("S",), field="bogus")
    rows = Rows()
    out = emit_rows(
        res, rows, "sweep/fleet_frontier", ("scheme", "S"), x="sim_time"
    )
    assert len(rows.rows) == len(out) == 6
    # x is ignored in streamed mode: no resampled budget column
    assert all("sim_time_budget" not in r[2] for r in rows.rows)
    assert all("final_accuracy=" in r[2] for r in rows.rows)


def test_fleet_frontier_registry_shape():
    spec = get_sweep("fleet_frontier", iters=8, runs=1)
    assert spec.reductions is not None
    assert spec.reductions.budgets and spec.reductions.quantiles
    cases = spec.cases()
    assert len(cases) == 12
    assert {c.response for c in cases} == {"lognormal", "pareto"}
    assert {c.scheme for c in cases} == {"cyclic", "mds", "approx"}
    assert all(
        (c.deadline is not None) == (c.scheme == "approx") for c in cases
    )


def test_heavy_tailed_responses():
    """Lognormal/Pareto draws: floor respected, mean excess ~= base_hi -
    base_lo (the equal-average-compute contract), Pareto tail heavier."""
    from repro.core.timing import TimingModel

    with pytest.raises(ValueError, match="unknown response"):
        TimingModel(response="cauchy")
    draws = {}
    for resp in ("lognormal", "pareto"):
        tm = TimingModel(
            response=resp, p_straggle=0.0, base_lo=1e-4, base_hi=2e-4
        )
        t = tm.sample_ecn_times(4000, 6, np.random.default_rng(0))
        assert t.min() >= tm.base_lo
        np.testing.assert_allclose(
            t.mean() - tm.base_lo, tm.base_hi - tm.base_lo, rtol=0.15
        )
        draws[resp] = t
    assert draws["pareto"].max() > draws["lognormal"].max()


def test_resample_runs_vectorized_matches_loop():
    """Satellite parity: the batched searchsorted must be bit-identical
    to the original per-run loop, including grid-tie and hold-first
    edge cases."""
    rng = np.random.default_rng(0)
    R, iters, n_points = 7, 50, 33
    xs = np.cumsum(rng.uniform(0.01, 1.0, size=(R, iters)), axis=1)
    # plant exact ties between grid points and xs values
    grid_ref = np.linspace(0.0, xs[:, -1].min(), n_points)
    xs[0, 3] = grid_ref[5]
    xs[1, 0] = grid_ref[0]  # = 0.0 tie at the grid origin
    xs = np.sort(xs, axis=1)
    ys = rng.normal(size=(R, iters))

    grid, out = resample_runs(xs, ys, n_points)
    np.testing.assert_array_equal(grid, grid_ref)
    loop = np.empty_like(out)
    for r in range(R):
        idx = np.searchsorted(xs[r], grid, side="right") - 1
        loop[r] = ys[r][np.clip(idx, 0, iters - 1)]
    np.testing.assert_array_equal(out, loop)
    with pytest.raises(ValueError, match="must be"):
        resample_runs(xs[0], ys[0])


def test_integer_fields_promote_to_float():
    """Satellite: integer-typed metrics (unit-count comm_cost) must not
    run CI math in integer arithmetic."""
    xs = np.cumsum(np.ones((3, 10)), axis=1)
    ys = np.arange(30, dtype=np.int32).reshape(3, 10)
    _, out = resample_runs(xs, ys, 8)
    assert np.issubdtype(out.dtype, np.floating)
    mean, ci = mean_ci(np.array([[1], [2]], dtype=np.int64))
    assert np.issubdtype(mean.dtype, np.floating)
    np.testing.assert_allclose(mean, [1.5])
    assert ci[0] > 0.0


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs a device mesh")
def test_chunked_streaming_uses_single_executable(monkeypatch):
    """Dispatch-count honesty: multi-chunk streaming must reuse ONE
    jitted executable (the max_statics_bound contract) — mixed-S chunks
    reconcile under one set of statics instead of retracing per chunk."""
    driver._batch_reduced_fn.cache_clear()
    kernel = get_kernel("csI-ADMM")
    D = len(jax.devices())
    probs, nets, cfgs = _admm_runs(D + 2)
    monkeypatch.setattr(driver, "_MEM_BUDGET", 0)
    run_sharded(kernel, probs, nets, cfgs, ITERS, reductions=FULL_SPEC)
    info = driver._batch_reduced_fn.cache_info()
    assert info.currsize == 1
