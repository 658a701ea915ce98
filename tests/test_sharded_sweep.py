"""Execution-mesh tests (DESIGN.md §9).

conftest.py forces an 8-CPU-device platform, so these tests exercise the
real sharded tier: the three execution tiers (serial scan, vmapped
batch, mesh-sharded batch) must agree elementwise, sharded must equal
vmapped BITWISE (SPMD partitioning of a runs axis no op crosses cannot
change per-run math), chunked dispatches must equal unchunked, and the
method step must lower through the fused Pallas hot path
`repro.kernels.ops.coded_admm_update`.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.core.admm import ADMMConfig
from repro.core.graph import make_network
from repro.core.problems import DATASETS, allocate
from repro.experiments import Case, SweepSpec, run_sweep
from repro.methods import Reduction, driver, get_kernel
from repro.methods.admm import ADMMRun

ITERS = 40
TRACE_FIELDS = (
    "accuracy", "test_error", "z_err", "comm_cost", "sim_time",
    "final_x", "final_z",
)

# conftest.py only setdefaults XLA_FLAGS: a developer running the suite
# with their own XLA_FLAGS legitimately gets a different device count.
# Skip (don't fail) in that case; in CI nothing sets XLA_FLAGS, so this
# module always runs there and test_forced_mesh_present pins that the
# conftest forcing actually took effect.
pytestmark = pytest.mark.skipif(
    len(jax.devices()) != 8,
    reason="suite running without the conftest 8-device forcing "
    "(external XLA_FLAGS set)",
)


def _spec(runs=3, S_values=(0, 1, 2)):
    """9-case fig5-style grid: deliberately NOT divisible by 8 devices,
    so the runs axis exercises the pad-to-device-multiple path."""
    return SweepSpec(
        "sharded_smoke",
        Case(
            method="csI-ADMM", dataset="usps", N=5, K=6, M=36,
            scheme="cyclic", iters=ITERS,
        ),
        axes={"S": list(S_values), "seed": list(range(runs))},
        fixup=lambda c: dataclasses.replace(
            c, scheme="uncoded" if c.S == 0 else c.scheme
        ),
    )


def test_forced_mesh_present():
    """When XLA_FLAGS is the conftest default, 8 devices MUST be visible
    (guards against the forcing silently rotting); the module-level
    skipif already routed externally-overridden runs away."""
    import os

    assert "host_platform_device_count=8" in os.environ.get("XLA_FLAGS", "")
    assert len(jax.devices()) == 8


def test_sharded_equals_vmapped_equals_serial():
    """The acceptance contract: sharded == vmapped bitwise, both == the
    per-run serial reference elementwise."""
    spec = _spec()
    sharded = run_sweep(spec, mode="sharded")
    batched = run_sweep(spec, mode="batched")
    serial = run_sweep(spec, mode="serial")
    assert sharded.mode == "sharded" and sharded.n_devices == 8
    assert batched.mode == "batched"
    assert sharded.cases == batched.cases == serial.cases
    assert sharded.n_dispatches == batched.n_dispatches == 1
    for case, tsh, tb, tse in zip(
        sharded.cases, sharded.traces, batched.traces, serial.traces
    ):
        for field in TRACE_FIELDS:
            np.testing.assert_array_equal(
                getattr(tsh, field), getattr(tb, field),
                err_msg=f"{case} field={field}: sharded != vmapped",
            )
            np.testing.assert_allclose(
                getattr(tsh, field), getattr(tse, field),
                rtol=1e-5, atol=1e-5,
                err_msg=f"{case} field={field}: sharded != serial",
            )


def test_auto_mode_resolves_to_sharded():
    """With 8 visible devices, "auto" (the default) picks the mesh tier."""
    result = run_sweep(_spec(runs=1, S_values=(0,)))
    assert result.mode == "sharded"
    assert result.n_devices == 8


def test_chunked_execution_matches_unchunked(monkeypatch):
    """A 1 MiB budget forces multiple device-aligned chunks; the split
    must be invisible in the outputs."""
    spec = _spec(runs=2)
    whole = run_sweep(spec, mode="sharded")
    monkeypatch.setattr(driver, "_MEM_BUDGET", 2**20)
    chunked = run_sweep(spec, mode="sharded")
    for tw, tc in zip(whole.traces, chunked.traces):
        for field in TRACE_FIELDS:
            np.testing.assert_array_equal(
                getattr(tw, field), getattr(tc, field), err_msg=field
            )


@pytest.mark.parametrize("budget", [None, 0], ids=["one-chunk", "chunks"])
@pytest.mark.parametrize("reduced", [False, True], ids=["traces", "summaries"])
@pytest.mark.parametrize("mode", ["batched", "sharded"])
def test_each_run_prepared_once(monkeypatch, mode, reduced, budget):
    """Every tier prepares each run exactly once, in one chunk or in
    several (a zero budget clamps a dispatch to the devices): the first
    run sizes the chunks as the first run of the first one."""
    kernel = get_kernel("csI-ADMM")
    prepare, seen = kernel.prepare, []

    def spy(problem, net, cfg, iters):
        seen.append((id(problem), repr(cfg)))  # one key per case
        return prepare(problem, net, cfg, iters)

    monkeypatch.setattr(kernel, "prepare", spy)
    if budget is not None:
        monkeypatch.setattr(driver, "_MEM_BUDGET", budget)
    spec = _spec()
    red = Reduction(fields=("accuracy",), budgets=(0.01,)) if reduced else None
    res = run_sweep(spec, mode=mode, reductions=red)
    assert len(res.cases) == 9
    assert len(seen) == 9 and len(set(seen)) == 9


def test_chunk_rule_device_aligned(monkeypatch):
    """Chunk sizes are multiples of D, at least D, at most R padded to a
    multiple of D, and as many runs as the budget holds with 2x slack."""
    monkeypatch.setattr(driver, "_MEM_BUDGET", 2**20)
    assert driver._chunk_runs(16, 8, run_bytes=10 * 2**20) == 8
    monkeypatch.setattr(driver, "_MEM_BUDGET", 4096 * 2**20)
    assert driver._chunk_runs(16, 8, run_bytes=10 * 2**20) == 16
    assert driver._chunk_runs(24, 4, run_bytes=1) == 24
    assert driver._chunk_runs(13, 4, run_bytes=1) == 16
    monkeypatch.setattr(driver, "_MEM_BUDGET", 64 * 2**20)
    assert driver._chunk_runs(512, 8, 2**20) == 256
    assert driver._chunk_runs(512, 8, 2**20 + 1) == 248
    assert driver._chunk_runs(1, 1, 2**40) == 1


def test_single_device_fallback(monkeypatch):
    """One visible device -> run_sharded degrades structurally to the
    single-device vmap (no mesh, no padding)."""
    spec = _spec(runs=1, S_values=(0, 1))
    batched = run_sweep(spec, mode="batched")
    one = jax.devices()[:1]
    monkeypatch.setattr(driver.jax, "devices", lambda *a: one)
    sharded = run_sweep(spec, mode="sharded")
    for tb, ts in zip(batched.traces, sharded.traces):
        np.testing.assert_array_equal(tb.accuracy, ts.accuracy)


def test_mode_validation():
    spec = _spec(runs=1, S_values=(0,))
    with pytest.raises(ValueError, match="unknown sweep mode"):
        run_sweep(spec, mode="bogus")
    assert run_sweep(spec, mode="serial").mode == "serial"


def test_step_lowers_through_coded_admm_update():
    """Kernel-routing pin: the ADMM family's composed run function must
    contain the fused Pallas decode-combine + x-update (DESIGN.md §5),
    not an unfused decode. I-ADMM (exact_x) keeps its closed-form solve
    and must NOT call it."""
    net = make_network(5, 0.5, seed=0)
    prob = allocate(DATASETS["usps"](0), 5, 3)
    kernel = get_kernel("sI-ADMM")

    def jaxpr_for(cfg):
        run = ADMMRun(cfg)
        prep = kernel.prepare(prob, net, run, 10)
        statics = {**prep.statics, **prep.max_statics}
        fn = driver._compose(kernel, driver._statics_key(statics))
        return str(jax.make_jaxpr(fn)(prep.consts, prep.steps))

    assert "coded_admm_update" in jaxpr_for(ADMMConfig(M=36, K=3))
    assert "coded_admm_update" not in jaxpr_for(
        ADMMConfig(M=36, K=3, exact_x=True)
    )
