"""Sweep-engine tests: vmapped grids must match per-run serial execution.

The acceptance contract of the engine (DESIGN.md §7): executing a grid as
batched vmapped scans is a pure performance transform — same seeds in,
same traces out, elementwise. The fig5-style grid below is the paper's
K x S x seed shape at smoke scale.
"""

import dataclasses

import numpy as np
import pytest

from repro.core.admm import ADMMConfig, run_incremental_admm
from repro.core.graph import make_network
from repro.core.problems import DATASETS, allocate
from repro.experiments import (
    Case,
    SweepSpec,
    get_sweep,
    mean_ci,
    reduce_mean,
    run_sweep,
    stack_field,
)
from repro.experiments.sweep import _signature

ITERS = 60


def _fig5_style_spec(runs=2, S_values=(0, 1, 2), iters=ITERS):
    """K=6 grid like fig5, shrunk (usps standin, M=36) for test time."""
    return SweepSpec(
        "fig5_smoke",
        Case(
            method="csI-ADMM", dataset="usps", N=5, K=6, M=36,
            scheme="cyclic", iters=iters,
        ),
        axes={"S": list(S_values), "seed": list(range(runs))},
        fixup=lambda c: dataclasses.replace(
            c, scheme="uncoded" if c.S == 0 else c.scheme
        ),
    )


def test_grid_expansion_and_dedupe():
    spec = _fig5_style_spec(runs=3)
    cases = spec.cases()
    assert len(cases) == 9
    assert {c.S for c in cases} == {0, 1, 2}
    assert all(c.scheme == ("uncoded" if c.S == 0 else "cyclic") for c in cases)
    # dict-valued axes + fixup dedupe: two axis points collapsing to the
    # same case appear once
    spec2 = SweepSpec(
        "dedupe",
        Case(),
        axes={"scheme": [{"S": 0, "scheme": "uncoded"},
                         {"S": 0, "scheme": "cyclic"}]},
        fixup=lambda c: dataclasses.replace(c, scheme="uncoded"),
    )
    assert len(spec2.cases()) == 1


def _windows_past_the_data(case) -> bool:
    """Whether the run, batched under its group's MU = M / K (an S = 0
    run's), fetches a window that runs past the end of the data: its last
    agent's last partition, at an offset within MU - mu of the end."""
    from repro.methods import get_kernel

    kernel = get_kernel(case.method)
    net = make_network(case.N, case.connectivity, seed=case.seed)
    prob = allocate(DATASETS[case.dataset](case.seed), case.N, case.K)
    prep = kernel.prepare(prob, net, kernel.config(case), case.iters)
    agents, offsets = prep.steps[0], prep.steps[1]
    last = (case.K - 1) * prep.statics["P"] + offsets + case.M // case.K
    return bool(np.any((agents == case.N - 1) & (last > prob.b)))


@pytest.mark.parametrize(
    "iters", [ITERS, 80], ids=["fig5", "pool_end"]
)
def test_vmapped_matches_serial_elementwise(iters):
    """Same seeds -> same traces, vmapped vs the per-run seed entry point.

    The group mixes S, so runs with mu < MU fetch windows that reach past
    their partitions; at 80 iterations every such run's last agent reads
    past the end of the data too."""
    spec = _fig5_style_spec(runs=2, iters=iters)
    if iters > ITERS:
        assert all(
            _windows_past_the_data(c) for c in spec.cases() if c.S > 0
        )
    batched = run_sweep(spec)
    serial = run_sweep(spec, mode="serial")
    assert batched.cases == serial.cases
    for case, tb, ts in zip(batched.cases, batched.traces, serial.traces):
        for field in ("accuracy", "test_error", "z_err", "comm_cost",
                      "sim_time", "final_x", "final_z"):
            np.testing.assert_allclose(
                getattr(tb, field), getattr(ts, field),
                rtol=1e-5, atol=1e-5, err_msg=f"{case} field={field}",
            )


def test_vmapped_matches_direct_seed_api():
    """Engine output == calling run_incremental_admm by hand (the seed
    implementation the figure scripts used before the engine existed)."""
    spec = _fig5_style_spec(runs=2, S_values=(0, 1))
    result = run_sweep(spec)
    for case, tr in zip(result.cases, result.traces):
        net = make_network(case.N, case.connectivity, seed=case.seed)
        prob = allocate(DATASETS[case.dataset](case.seed), case.N, case.K)
        ref = run_incremental_admm(
            prob, net, case.admm_config(), case.iters,
            straggler=case.timing_model(),
        )
        np.testing.assert_allclose(
            tr.accuracy, ref.accuracy, rtol=1e-5, atol=1e-5,
            err_msg=str(case),
        )


def test_single_dispatch_per_static_group():
    """The whole S x seed grid costs ONE batched dispatch: the sub-batch
    size mu = M/((S+1)K) is a runtime input of the masked batched scan,
    so different S values share a static signature (and one jit trace)."""
    spec = _fig5_style_spec(runs=3)
    result = run_sweep(spec)
    assert len(result.cases) == 9
    assert result.n_dispatches == 1
    assert [n for _, n in result.groups] == [9]
    sigs = {_signature(c, allocate(DATASETS[c.dataset](c.seed), c.N, c.K))
            for c in result.cases}
    assert len(sigs) == 1


def test_baseline_methods_batch_and_match():
    cases = [
        Case(method=m, dataset="usps", N=5, K=3, M=33, iters=40, seed=s)
        for m in ("W-ADMM", "D-ADMM", "DGD", "EXTRA")
        for s in (0, 1)
    ]
    batched = run_sweep(cases)
    serial = run_sweep(cases, mode="serial")
    assert batched.n_dispatches == 4  # one vmapped dispatch per method
    for case, tb, ts in zip(cases, batched.traces, serial.traces):
        np.testing.assert_allclose(
            tb.accuracy, ts.accuracy, rtol=1e-5, atol=1e-5,
            err_msg=str(case),
        )
        np.testing.assert_allclose(tb.comm_cost, ts.comm_cost)


def test_mean_reduction_matches_numpy():
    spec = _fig5_style_spec(runs=3)
    result = run_sweep(spec)
    red = reduce_mean(result, by=("S",))
    assert set(red) == {(0,), (1,), (2,)}
    for (S,), r in red.items():
        runs = stack_field(
            [t for c, t in zip(result.cases, result.traces) if c.S == S],
            "accuracy",
        )
        assert r["n"] == 3
        np.testing.assert_allclose(r["mean"], runs.mean(axis=0))
        # CI: 1.96 * sample std / sqrt(n)
        np.testing.assert_allclose(
            r["ci"], 1.96 * runs.std(axis=0, ddof=1) / np.sqrt(3)
        )
    # n=1 groups get zero-width CI
    m, ci = mean_ci(np.ones((1, 5)))
    np.testing.assert_allclose(ci, 0.0)


def test_mixed_statics_rejected_by_batch_driver():
    from repro.methods import get_kernel, run_batch
    from repro.methods.admm import ADMMRun

    kernel = get_kernel("sI-ADMM")
    nets = [make_network(5, 0.5, seed=s) for s in (0, 1)]
    probs = [allocate(DATASETS["usps"](s), 5, k) for s, k in ((0, 3), (1, 6))]
    cfgs = [
        ADMMRun(ADMMConfig(M=12, K=3, seed=0)),
        ADMMRun(ADMMConfig(M=12, K=6, seed=1)),
    ]
    with pytest.raises(ValueError, match="static signatures"):
        run_batch(kernel, probs, nets, cfgs, 10)

    # ...but mixed mini-batch sizes M (hence mixed mu) batch fine: mu is a
    # runtime input of the masked kernel step, not a jit static.
    probs = [allocate(DATASETS["usps"](s), 5, 3) for s in (0, 1)]
    cfgs = [
        ADMMRun(ADMMConfig(M=12, K=3, seed=0)),
        ADMMRun(ADMMConfig(M=24, K=3, seed=1)),
    ]
    traces = run_batch(kernel, probs, nets, cfgs, 20)
    for prob, net, run, tr in zip(probs, nets, cfgs, traces):
        ref = run_incremental_admm(prob, net, run.cfg, 20)
        np.testing.assert_allclose(
            tr.accuracy, ref.accuracy, rtol=1e-5, atol=1e-5
        )


def test_registry_sweeps_resolve():
    from repro.experiments import SWEEPS

    for name in SWEEPS:
        spec = get_sweep(name, iters=8, runs=1)
        cases = spec.cases()
        assert cases, name
        for c in cases:
            if c.method in (
                "sI-ADMM", "csI-ADMM", "I-ADMM", "pI-ADMM", "cq-sI-ADMM"
            ):
                c.admm_config().validate()

    with pytest.raises(KeyError):
        get_sweep("nonexistent")


# Pinned grid shape of every named sweep at (iters=8, runs=1):
# (n_cases, n_static_groups). Registry edits that change how many traces a
# sweep compiles or how many runs it dispatches must update this table —
# trace counts can't silently explode.
EXPECTED_GRIDS = {
    "fig3_minibatch": (4, 1),  # M is runtime (masked mu): one trace
    "fig3_baselines": (5, 5),  # one method = one kernel = one trace
    "fig3_stragglers": (9, 2),  # K=4 fractional splits off (b, K differ)
    "fig3e_runtime": (5, 5),  # one method = one kernel = one trace
    "fig4_baselines": (5, 5),
    "fig4_stragglers": (2, 1),  # S/scheme are runtime: one trace
    "fig5": (4, 1),  # the tentpole: whole S sweep shares one trace
    "topology_grid": (15, 1),  # S=0 scheme points merge; eta is runtime
    "code_frontier": (10, 1),  # deadline merges for exact families
    "adaptive_frontier": (2, 2),  # arms are runtime; one group per algo

    "privacy_grid": (8, 1),  # sigma and S are runtime: one trace
    "compression_grid": (9, 3),  # one trace per compressor static
    "hetero_grid": (15, 1),  # speed classes are host-side clock only
    "mesh_scale": (3, 1),  # S=0 schemes merge; S/scheme are runtime
    "fleet_frontier": (12, 1),  # response/scheme/deadline/S all runtime
    # per method: one sync group (tau_max=0) + one async ring group
    "staleness_frontier": (16, 8),
    "churn_grid": (9, 2),  # churn_rate=0 keeps the sync signature
}


def test_registry_sweep_counts():
    """Smoke-materialize every named sweep; pin case and group counts."""
    from repro.experiments import SWEEPS
    from repro.experiments.sweep import _materialize

    assert set(EXPECTED_GRIDS) == set(SWEEPS)
    for name, (n_cases, n_groups) in EXPECTED_GRIDS.items():
        spec = get_sweep(name, iters=8, runs=1)
        cases = spec.cases()
        net_cache, prob_cache = {}, {}
        sigs = {
            _signature(c, _materialize(c, net_cache, prob_cache)[1])
            for c in cases
        }
        assert len(cases) == n_cases, f"{name}: {len(cases)} cases"
        assert len(sigs) == n_groups, f"{name}: {len(sigs)} static groups"


def test_mesh_scale_default_grid_is_48():
    """The 2 (S) x 2 (scheme) x 16 (seed) axis product is 64 points, but
    the S=0 cyclic/fractional points dedupe to one uncoded case per seed:
    48 runs — what the docstring promises and the mesh actually sees."""
    assert len(get_sweep("mesh_scale").cases()) == 48
