"""Unified timing model tests (DESIGN.md §10).

The contract: EVERY registered method kernel emits an honest simulated
wall-clock — strictly increasing, positive ``sim_time`` (the guard that
keeps future methods from re-introducing the ``zeros(iters)``
placeholder) — and the time-axis reduction turns those clocks into a
seed-averaged accuracy-vs-running-time curve that all execution tiers
agree on elementwise.
"""

import jax
import numpy as np
import pytest

from repro.core.admm import ADMMConfig, make_schedule
from repro.core.coding import make_code
from repro.core.graph import make_network
from repro.core.problems import DATASETS, allocate
from repro.core.timing import StragglerModel, TimingModel
from repro.experiments import (
    Case,
    get_sweep,
    reduce_mean,
    resample_runs,
    run_sweep,
)
from repro.experiments.sweep import METHODS
from repro.methods import get_kernel

ITERS = 40


def _case(method: str, **kw) -> Case:
    incremental = method not in ("D-ADMM", "DGD", "EXTRA", "W-ADMM")
    kw.setdefault("M", 36 if incremental else 33)
    if method == "csI-ADMM":
        kw.setdefault("S", 1)
        kw.setdefault("scheme", "cyclic")
    if method == "a-csI-ADMM":
        kw.setdefault(
            "arms", (("cyclic", 1, None), ("approx", 1, 3e-4))
        )
    return Case(method=method, dataset="usps", N=5, K=3, iters=ITERS, **kw)


def _prepared(case: Case):
    kernel = get_kernel(case.method)
    net = make_network(case.N, case.connectivity, seed=case.seed)
    prob = allocate(DATASETS[case.dataset](case.seed), case.N, case.K)
    return kernel.prepare(prob, net, kernel.config(case), case.iters)


# -------------------------------------------------------------------------
# the zeros(iters) guard: every kernel's clock is real
# -------------------------------------------------------------------------


@pytest.mark.parametrize("method", sorted(METHODS))
def test_every_kernel_emits_increasing_positive_time(method):
    """sim_time and comm_cost are cumulative: positive and strictly
    increasing for EVERY registered kernel — no constant-zero placeholders."""
    prep = _prepared(_case(method))
    for field in ("sim_time", "comm"):
        series = np.asarray(getattr(prep, field))
        assert series.shape == (ITERS,), (method, field)
        assert series[0] > 0, (method, field)
        assert (np.diff(series) > 0).all(), (method, field)


def test_gossip_round_dominates_incremental_hop():
    """A gossip round waits for the slowest of N agents plus serialized
    neighbor transfers — per iteration it must cost at least as much as
    any single agent's compute draw, and in expectation more than the
    single-agent walk step."""
    si = _prepared(_case("sI-ADMM")).sim_time[-1]
    dgd = _prepared(_case("DGD")).sim_time[-1]
    assert dgd > si * 0.5  # same order of magnitude: one unified clock
    model = TimingModel(p_straggle=0.0)
    net = make_network(6, 0.6, seed=0)
    rng = np.random.default_rng(0)
    rounds = model.gossip_round_times(net, 500, rng)
    # every round >= base_lo compute + max-degree * comm_lo transfers
    floor = model.base_lo + net.degree().max() * model.comm_lo
    assert (rounds >= floor).all()


# -------------------------------------------------------------------------
# uncoded straggler fallback (satellite bugfix)
# -------------------------------------------------------------------------


def test_uncoded_fallback_records_true_wait():
    """When NO ECN beats epsilon, the agent waits out the fastest ECN —
    the recorded response must be that (> epsilon) wait, not the cap."""
    cfg = ADMMConfig(M=36, K=3, scheme="uncoded")
    net = make_network(5, 0.5, seed=0)
    # base compute 10-20x the cap: every iteration falls back
    model = TimingModel(
        base_lo=1e-3, base_hi=2e-3, p_straggle=0.0, epsilon=1e-4
    )
    sched = make_schedule(
        cfg, net, make_code("uncoded", 3, 0), model, 200, b=36 * 3
    )
    assert (sched["resp_time"] > model.epsilon).all()
    # the wait is exactly the fastest ECN's response on every fallback row
    rng = np.random.default_rng(cfg.seed + 1)
    ecn_t = model.sample_ecn_times(200, cfg.K, rng)
    np.testing.assert_allclose(sched["resp_time"], ecn_t.min(axis=1))
    # ...and the decode weights use only that fastest ECN (weight K)
    assert (np.sort(sched["decode"], axis=1)[:, :-1] == 0).all()
    assert (sched["decode"].max(axis=1) == cfg.K).all()


def test_uncoded_cap_still_applies_when_someone_responds():
    cfg = ADMMConfig(M=36, K=3, scheme="uncoded")
    net = make_network(5, 0.5, seed=0)
    model = TimingModel(p_straggle=0.5, delay=1e-2, epsilon=2e-3)
    sched = make_schedule(
        cfg, net, make_code("uncoded", 3, 0), model, 500, b=36 * 3
    )
    rng = np.random.default_rng(cfg.seed + 1)
    ecn_t = model.sample_ecn_times(500, cfg.K, rng)
    responded = (ecn_t <= model.epsilon).any(axis=1)
    assert responded.any() and not responded.all()
    assert (sched["resp_time"][responded] <= model.epsilon).all()
    assert (
        sched["resp_time"][~responded] == ecn_t[~responded].min(axis=1)
    ).all()


# -------------------------------------------------------------------------
# heterogeneous fleet knobs
# -------------------------------------------------------------------------


def test_speed_classes_scale_worker_times():
    rng_hom = np.random.default_rng(7)
    rng_het = np.random.default_rng(7)
    hom = TimingModel(p_straggle=0.0).sample_ecn_times(300, 4, rng_hom)
    het = TimingModel(
        p_straggle=0.0, speed_classes=(1.0, 3.0)
    ).sample_ecn_times(300, 4, rng_het)
    # round-robin assignment: workers 0/2 untouched, workers 1/3 3x slower
    np.testing.assert_allclose(het[:, ::2], hom[:, ::2])
    np.testing.assert_allclose(het[:, 1::2], 3.0 * hom[:, 1::2])


def test_shifted_exp_response_floor_and_tail():
    model = TimingModel(p_straggle=0.0, response="shifted_exp")
    t = model.sample_ecn_times(2000, 3, np.random.default_rng(0))
    assert (t >= model.base_lo).all()
    # exponential tail: some draws exceed the uniform model's hard cap
    assert (t > model.base_hi).any()
    mean = model.base_lo + (model.base_hi - model.base_lo)
    assert t.mean() == pytest.approx(mean, rel=0.1)


def test_timing_model_validation():
    with pytest.raises(ValueError, match="unknown response"):
        TimingModel(response="gaussian")
    with pytest.raises(ValueError, match="speed_classes"):
        TimingModel(speed_classes=())
    with pytest.raises(ValueError, match="speed_classes"):
        TimingModel(speed_classes=(1.0, -2.0))
    # the paper-era name is the same class, homogeneous-uniform defaults
    assert StragglerModel is TimingModel


def test_hetero_slowdown_reaches_the_admm_clock():
    """A uniformly 4x slower fleet must produce a ~4x slower response
    path end-to-end through Case -> kernel.prepare (p_straggle=0 so the
    additive straggler delay doesn't blur the ratio)."""
    fast = _prepared(_case("csI-ADMM", S=1, p_straggle=0.0))
    slow = _prepared(
        _case("csI-ADMM", S=1, p_straggle=0.0, speed_classes=(4.0,))
    )
    assert slow.sim_time[-1] > 2.0 * fast.sim_time[-1]


# -------------------------------------------------------------------------
# deadline-aware decode (DESIGN.md §11)
# -------------------------------------------------------------------------


def _coded_schedule(scheme: str, K: int, S: int, model: TimingModel, iters=400):
    cfg = ADMMConfig(M=(S + 1) * K * 4, K=K, S=S, scheme=scheme)
    net = make_network(5, 0.5, seed=0)
    code = make_code(scheme, K, S, seed=cfg.seed)
    sched = make_schedule(cfg, net, code, model, iters, b=cfg.M * 2)
    rng = np.random.default_rng(cfg.seed + 1)
    ecn_t = model.sample_ecn_times(iters, K, rng)
    return code, sched, ecn_t


def test_deadline_decode_records_deadline_not_ecn_wait():
    """The satellite guard: iterations that decode at the deadline must
    record the DEADLINE as their response time — not the R-th (slowest
    counted) ECN wait — and their decode vectors must be supported on
    exactly the ECNs that had arrived by the deadline."""
    model = TimingModel(p_straggle=0.3, delay=5e-3, deadline=3e-4)
    code, sched, ecn_t = _coded_schedule("approx", 6, 2, model)
    arrived = ecn_t <= model.deadline
    n_arr = arrived.sum(axis=1)
    order = np.sort(ecn_t, axis=1)
    t_exact = order[:, code.R - 1]
    fired = (n_arr >= code.min_responses) & (n_arr < code.R)
    assert fired.any() and not fired.all()  # both paths exercised
    np.testing.assert_allclose(
        sched["resp_time"][fired], model.deadline
    )
    # the deadline wait is strictly shorter than the exact-decode wait
    assert (model.deadline < t_exact[fired]).all()
    # non-deadline rows keep the epsilon-capped R-th fastest response
    np.testing.assert_allclose(
        sched["resp_time"][~fired],
        np.minimum(t_exact[~fired], model.epsilon),
    )
    # decode supported on the arrived set only, alive mask recorded
    np.testing.assert_array_equal(sched["alive"][fired], arrived[fired])
    assert (sched["decode"][fired][~arrived[fired]] == 0).all()


def test_deadline_below_rmin_falls_back_to_exact_wait():
    """A deadline nobody can meet (shorter than every base draw) never
    fires: every iteration decodes exactly at the R-th response."""
    model = TimingModel(p_straggle=0.3, delay=5e-3, deadline=1e-6)
    code, sched, ecn_t = _coded_schedule("approx", 6, 2, model)
    t_exact = np.sort(ecn_t, axis=1)[:, code.R - 1]
    np.testing.assert_allclose(
        sched["resp_time"], np.minimum(t_exact, model.epsilon)
    )


def test_deadline_above_epsilon_never_fires():
    """'Whichever fires first' also holds against the epsilon cap: a
    deadline armed ABOVE epsilon can never beat the exact path's capped
    wait, so it must not fire (firing would record a LONGER wait plus a
    decode error)."""
    model = TimingModel(
        p_straggle=0.3, delay=5e-3, epsilon=1e-3, deadline=2e-3
    )
    exact = TimingModel(p_straggle=0.3, delay=5e-3, epsilon=1e-3)
    _, s_dl, _ = _coded_schedule("approx", 6, 2, model)
    _, s_ex, _ = _coded_schedule("approx", 6, 2, exact)
    for f in ("resp_time", "decode", "alive"):
        np.testing.assert_array_equal(s_dl[f], s_ex[f])
    assert (s_dl["resp_time"] <= model.epsilon).all()


def test_deadline_noop_for_exact_families():
    """Exact-only families (min_responses == R) ignore the deadline: the
    schedule is bit-identical with and without it."""
    with_dl = TimingModel(p_straggle=0.3, deadline=3e-4)
    without = TimingModel(p_straggle=0.3)
    for scheme in ("cyclic", "fractional"):
        _, s1, _ = _coded_schedule(scheme, 6, 2, with_dl)
        _, s2, _ = _coded_schedule(scheme, 6, 2, without)
        for f in ("resp_time", "decode", "alive"):
            np.testing.assert_array_equal(s1[f], s2[f], err_msg=scheme)


def test_deadline_shortens_admm_clock_end_to_end():
    """Case -> kernel.prepare: a deadline-decoding run's cumulative
    sim_time is strictly below the exact-decode run's (same draws)."""
    base = dict(scheme="approx", S=1, p_straggle=0.3, delay=5e-3)
    exact = _prepared(_case("csI-ADMM", **base))
    dl = _prepared(_case("csI-ADMM", **base, deadline=3e-4))
    assert dl.sim_time[-1] < exact.sim_time[-1]


def test_timing_model_deadline_validation():
    with pytest.raises(ValueError, match="deadline"):
        TimingModel(deadline=0.0)
    with pytest.raises(ValueError, match="deadline"):
        TimingModel(deadline=-1e-3)
    assert TimingModel(deadline=None).deadline is None


def test_code_frontier_single_dispatch_and_tier_agreement():
    """Acceptance criterion: the code_frontier grid is ONE dispatch, and
    serial/batched/sharded agree elementwise on the sim_time-axis
    reduction (the sweep's declared headline axis)."""
    spec = get_sweep("code_frontier", iters=40, runs=2)
    batched = run_sweep(spec, mode="batched")
    assert batched.n_dispatches == 1
    assert len(batched.cases) == 20
    modes = [batched, run_sweep(spec, mode="serial")]
    if len(jax.devices()) > 1:
        modes.append(run_sweep(spec, mode="sharded"))
    reds = [
        reduce_mean(r, by=("scheme", "S", "deadline"), x="sim_time",
                    n_points=48)
        for r in modes
    ]
    assert len(reds[0]) == 10
    for key, r in reds[0].items():
        assert r["n"] == 2
        assert np.isfinite(r["mean"]).all(), key
        for other in reds[1:]:
            np.testing.assert_allclose(
                r["mean"], other[key]["mean"], rtol=1e-5, atol=1e-5,
                err_msg=f"tiers disagree on {key}",
            )


# -------------------------------------------------------------------------
# time-axis reduction + tier agreement (acceptance criterion)
# -------------------------------------------------------------------------


def test_resample_runs_step_function():
    xs = np.array([[1.0, 2.0, 4.0], [1.0, 3.0, 5.0]])
    ys = np.array([[9.0, 8.0, 7.0], [6.0, 5.0, 4.0]])
    grid, vals = resample_runs(xs, ys, n_points=5)
    np.testing.assert_allclose(grid, [0.0, 1.0, 2.0, 3.0, 4.0])
    # run 0: first value held before t=1, steps at 1/2/4
    np.testing.assert_allclose(vals[0], [9.0, 9.0, 8.0, 8.0, 7.0])
    np.testing.assert_allclose(vals[1], [6.0, 6.0, 6.0, 5.0, 5.0])
    with pytest.raises(ValueError, match="R, iters"):
        resample_runs(xs[0], ys[0])


def test_fig3e_runtime_reduction_and_tier_agreement():
    """The acceptance contract: fig3e_runtime reduces to a monotone
    per-method accuracy-vs-time curve via reduce_mean(x="sim_time"), and
    serial/batched(/sharded) tiers agree on it elementwise."""
    spec = get_sweep("fig3e_runtime", iters=60, runs=2)
    batched = run_sweep(spec, mode="batched")
    serial = run_sweep(spec, mode="serial")
    modes = [batched, serial]
    if len(jax.devices()) > 1:
        modes.append(run_sweep(spec, mode="sharded"))
    reds = [
        reduce_mean(r, by=("method",), x="sim_time", n_points=64)
        for r in modes
    ]
    assert set(reds[0]) == {
        (m,) for m in ("sI-ADMM", "W-ADMM", "D-ADMM", "DGD", "EXTRA")
    }
    for key, r in reds[0].items():
        assert r["n"] == 2
        grid = r["x"]
        assert grid[0] == 0.0 and (np.diff(grid) > 0).all(), key
        assert np.isfinite(r["mean"]).all(), key
        # relative error starts near 1 and must have improved by budget
        assert r["mean"][-1] < r["mean"][0], key
        for other in reds[1:]:
            np.testing.assert_allclose(
                r["mean"], other[key]["mean"], rtol=1e-5, atol=1e-5,
                err_msg=f"tiers disagree on {key}",
            )
            np.testing.assert_allclose(grid, other[key]["x"], rtol=1e-12)


def test_gossip_timing_deterministic_per_seed():
    """Same Case -> same clock (host draws are seeded); different seeds
    -> different clocks (independent straggler realizations)."""
    a = _prepared(_case("EXTRA", seed=0)).sim_time
    b = _prepared(_case("EXTRA", seed=0)).sim_time
    c = _prepared(_case("EXTRA", seed=1)).sim_time
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


def test_compressed_token_ships_faster_link():
    """cq-sI-ADMM's compressed hops scale LINK time by their true bit
    cost, while the ECN response term is untouched — total simulated
    time sits strictly between response-only and the dense-token clock."""
    dense = _prepared(_case("sI-ADMM", p_straggle=0.0))
    comp = _prepared(
        _case("cq-sI-ADMM", p_straggle=0.0, compressor="topk", frac=0.25)
    )
    assert comp.sim_time[-1] < dense.sim_time[-1]


def test_hetero_grid_single_dispatch():
    """Speed classes touch only the host-side clock, so the whole
    heterogeneity grid still batches into ONE dispatch — and a slower
    mix can only push every matched (S, scheme, seed) arm's clock out
    (same base draws, scaled up)."""
    spec = get_sweep("hetero_grid", iters=8, runs=1)
    result = run_sweep(spec)
    assert len(result.cases) == 15
    assert result.n_dispatches == 1
    finals = {
        (c.speed_classes, c.S, c.scheme): t.sim_time[-1]
        for c, t in zip(result.cases, result.traces)
    }
    pairs = [
        (finals[((1.0,), S, scheme)], finals[((1.0, 1.0, 4.0), S, scheme)])
        for (sc, S, scheme) in finals if sc == (1.0,)
    ]
    assert all(hom <= het for hom, het in pairs)
    assert any(hom < het for hom, het in pairs)
