"""MethodKernel protocol tests (DESIGN.md §8).

The contract: every registered method has ONE step implementation, and
the batched engine (`vmap` of that step) matches the serial driver
(`lax.scan` of that step) elementwise — for the paper's six algorithms
AND the two beyond-paper variants that ship through the protocol only
(pI-ADMM privacy noise, cq-sI-ADMM compressed tokens).
"""

import numpy as np
import pytest

from repro.core.admm import ADMMConfig, run_incremental_admm
from repro.core.graph import make_network
from repro.core.problems import DATASETS, allocate
from repro.experiments import Case, run_sweep
from repro.experiments.sweep import METHODS
from repro.methods import KERNELS, get_kernel, run_serial
from repro.methods.admm import ADMMRun
from repro.methods.compression import CompressionRun

ITERS = 40
ALL_METHODS = (
    "sI-ADMM", "csI-ADMM", "I-ADMM", "W-ADMM", "D-ADMM", "DGD", "EXTRA",
    "pI-ADMM", "cq-sI-ADMM", "a-csI-ADMM",
)


def _case(method: str, seed: int = 0, **kw) -> Case:
    incremental = method not in ("D-ADMM", "DGD", "EXTRA", "W-ADMM")
    kw.setdefault("M", 36 if incremental else 33)
    if method == "csI-ADMM":
        kw.setdefault("S", 1)
        kw.setdefault("scheme", "cyclic")
    if method == "a-csI-ADMM":
        kw.setdefault(
            "arms", (("cyclic", 1, None), ("approx", 1, 3e-4))
        )
    return Case(
        method=method, dataset="usps", N=5, K=3, iters=ITERS, seed=seed, **kw
    )


def test_registry_covers_every_method():
    assert set(METHODS) == set(KERNELS) == set(ALL_METHODS)
    with pytest.raises(KeyError, match="unknown method"):
        get_kernel("nope")


def test_batched_matches_serial_every_method():
    """vmap-of-step == scan-of-step elementwise, for all ten kernels."""
    cases = [_case(m, seed=s) for m in ALL_METHODS for s in (0, 1)]
    batched = run_sweep(cases)
    serial = run_sweep(cases, mode="serial")
    # sI and csI share the ADMM family signature (S/scheme are runtime
    # inputs) and merge into one dispatch; every other method is its own.
    assert batched.n_dispatches == len(ALL_METHODS) - 1
    for case, tb, ts in zip(cases, batched.traces, serial.traces):
        for field in ("accuracy", "test_error", "z_err", "comm_cost",
                      "sim_time", "final_x", "final_z"):
            np.testing.assert_allclose(
                getattr(tb, field), getattr(ts, field),
                rtol=1e-5, atol=1e-5, err_msg=f"{case.method} field={field}",
            )
        assert np.isfinite(tb.accuracy).all(), case.method


def test_piadmm_sigma_zero_is_exactly_siadmm():
    """The noise-free control arm of the privacy kernel is sI-ADMM."""
    case = _case("pI-ADMM", sigma=0.0)
    net = make_network(case.N, case.connectivity, seed=case.seed)
    prob = allocate(DATASETS[case.dataset](case.seed), case.N, case.K)
    kernel = get_kernel("pI-ADMM")
    tr = run_serial(kernel, prob, net, kernel.config(case), ITERS)
    ref = run_incremental_admm(prob, net, case.admm_config(), ITERS)
    np.testing.assert_allclose(tr.accuracy, ref.accuracy, rtol=1e-12)
    np.testing.assert_allclose(
        tr.final_z, ref.final_z, rtol=1e-12, atol=1e-13
    )


def test_piadmm_noise_perturbs_iterates():
    case = _case("pI-ADMM", sigma=0.5)
    net = make_network(case.N, case.connectivity, seed=case.seed)
    prob = allocate(DATASETS[case.dataset](case.seed), case.N, case.K)
    kernel = get_kernel("pI-ADMM")
    tr = run_serial(kernel, prob, net, kernel.config(case), ITERS)
    ref = run_incremental_admm(prob, net, case.admm_config(), ITERS)
    assert np.abs(tr.final_z - ref.final_z).max() > 1e-6


def test_cq_topk_full_fraction_is_exactly_siadmm():
    """frac=1.0 keeps every token entry: the compressor is the identity
    and the error-feedback accumulator stays exactly zero."""
    case = _case("cq-sI-ADMM", compressor="topk", frac=1.0)
    net = make_network(case.N, case.connectivity, seed=case.seed)
    prob = allocate(DATASETS[case.dataset](case.seed), case.N, case.K)
    kernel = get_kernel("cq-sI-ADMM")
    tr = run_serial(kernel, prob, net, kernel.config(case), ITERS)
    ref = run_incremental_admm(prob, net, case.admm_config(), ITERS)
    np.testing.assert_allclose(tr.accuracy, ref.accuracy, rtol=1e-12)
    # atol: the two kernels compile into separately-fused executables of
    # the same step math; XLA's fusion choices around the Pallas x-update
    # may differ by reassociation, so equality is ULP-level, not bitwise.
    np.testing.assert_allclose(
        tr.final_z, ref.final_z, rtol=1e-12, atol=1e-13
    )


def test_cq_comm_accounting():
    """Compressed token hops are charged their true bit cost, side
    information included (quant: sign + per-token scale; topk: indices),
    relative to the 32-bit dense token's 1 unit."""
    net = make_network(5, 0.5, seed=0)
    prob = allocate(DATASETS["usps"](0), 5, 3)
    pd = prob.p * prob.d
    kernel = get_kernel("cq-sI-ADMM")
    run = CompressionRun(ADMMConfig(M=36, K=3), compressor="quant", bits=8)
    tr = run_serial(kernel, prob, net, run, ITERS)
    assert tr.comm_cost[-1] == pytest.approx(
        ITERS * ((8 + 1) * pd + 32) / (32 * pd)
    )
    run = CompressionRun(ADMMConfig(M=36, K=3), compressor="topk", frac=0.25)
    tr = run_serial(kernel, prob, net, run, ITERS)
    k = int(np.ceil(0.25 * pd))
    idx_bits = int(np.ceil(np.log2(pd)))
    assert tr.comm_cost[-1] == pytest.approx(
        ITERS * k * (32 + idx_bits) / (32 * pd)
    )
    # compression must actually pay off versus the dense token
    assert tr.comm_cost[-1] < ITERS


def test_cq_compressed_still_converges():
    """Error feedback keeps compressed tokens on the sI-ADMM path: both
    compressors end within a small factor of the uncompressed error."""
    net = make_network(5, 0.5, seed=0)
    prob = allocate(DATASETS["usps"](0), 5, 3)
    iters = 600
    ref = run_incremental_admm(
        prob, net, ADMMConfig(M=36, K=3, c_tau=0.5), iters
    )
    kernel = get_kernel("cq-sI-ADMM")
    for kw in (dict(compressor="topk", frac=0.25),
               dict(compressor="quant", bits=8)):
        run = CompressionRun(ADMMConfig(M=36, K=3, c_tau=0.5), **kw)
        tr = run_serial(kernel, prob, net, run, iters)
        assert tr.z_err[-1] < max(3.0 * ref.z_err[-1], 0.1), kw


def test_config_validation_errors():
    net = make_network(5, 0.5, seed=0)
    prob = allocate(DATASETS["usps"](0), 5, 3)
    kernel = get_kernel("cq-sI-ADMM")
    with pytest.raises(ValueError, match="frac"):
        run_serial(
            kernel, prob, net,
            CompressionRun(ADMMConfig(M=36, K=3), compressor="topk", frac=0.0),
            10,
        )
    with pytest.raises(ValueError, match="unknown compressor"):
        run_serial(
            kernel, prob, net,
            CompressionRun(ADMMConfig(M=36, K=3), compressor="nope"),
            10,
        )
    with pytest.raises(ValueError, match="code does not match"):
        from repro.core.coding import make_code

        run_serial(
            get_kernel("csI-ADMM"), prob, net,
            ADMMRun(
                ADMMConfig(M=36, K=3, S=1, scheme="cyclic"),
                code=make_code("cyclic", 3, 2),
            ),
            10,
        )
