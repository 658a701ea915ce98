"""The check that decides ``correct`` for sweep cells, on the CPU at a
size a test run holds: the fleet cell's grid with one seed per job and
60 iterations a run, driven through the harness with the look for a chip
skipped. A sound run passes; the plain reference in bfloat16 put in the
program's place (the control) fails; and so does a run with the timed
path broken underneath: a step that returns its state unchanged, half of
each mini-batch left out with the mean taken over the rest, and an
answer altered where the fold produces it. The cell runs on one chip, so
no exchange between chips can be left out."""

import copy
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402

CELL = "fleet_synth.stream"
SEED = 2**31 + 11


@pytest.fixture(scope="module")
def tiny():
    traffic = copy.deepcopy(harness.resolve(CELL)["traffic"])
    traffic.update(seeds_per_job=1, iters=60)
    return traffic


@pytest.fixture
def fresh_programs():
    """Each case traces the step anew, so a planted fault is compiled in:
    the batched tier caches its compiled scan per kernel and summaries."""
    from repro.methods import run_batch

    cached = run_batch.__globals__["_batch_reduced_fn"]
    cached.cache_clear()
    yield
    cached.cache_clear()


def run(traffic, monkeypatch):
    """One run of the cell through the harness on this host's first device,
    with the tiny traffic in place of the cell's."""
    import jax

    resolve, peaks = harness.resolve, harness.peaks

    def tiny_resolve(cell, root=harness.ROOT):
        return dict(resolve(cell, root), traffic=traffic)

    monkeypatch.setattr(harness, "resolve", tiny_resolve)
    monkeypatch.setattr(harness, "devices", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "peaks", lambda kind: peaks("TPU v5 lite"))
    return harness.run_cell(CELL, SEED, 0.0, False, t_start=time.perf_counter(),
                            log=lambda msg: None)


def test_sound_run_is_correct(tiny, fresh_programs, monkeypatch):
    res = run(tiny, monkeypatch)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 12 and res["failed"] == 0
    assert list(res)[-1] == "checks"


def test_control_in_bfloat16_is_not_correct(tiny):
    r = harness.resolve(CELL)
    checks = r["generator"].Workload(r["config"], tiny, SEED).control("bfloat16")
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


def _state_unchanged(monkeypatch):
    from repro.methods.admm import IncrementalADMM

    def step(self, state, inp, aux, statics):
        return state, self.metrics(state["x"], state["z"], aux)

    monkeypatch.setattr(IncrementalADMM, "step", step)


def _half_batch(monkeypatch):
    from repro.methods.admm import IncrementalADMM

    setup = IncrementalADMM.setup

    def half(self, consts, statics):
        aux = setup(self, consts, statics)
        keep = consts[6] // 2  # the runtime mini-batch size mu, halved
        aux["valid"] = (aux["rows"] < keep).astype(aux["dtype"])
        aux["inv_mu"] = 1.0 / keep.astype(aux["dtype"])
        return aux

    monkeypatch.setattr(IncrementalADMM, "setup", half)


def _answer_altered(monkeypatch):
    from repro.methods.reductions import Reduction

    finalize = Reduction.finalize_carry

    def altered(self, carry):
        out = finalize(self, carry)
        out["accuracy/final"] = out["accuracy/final"] * 1.01
        return out

    monkeypatch.setattr(Reduction, "finalize_carry", altered)


@pytest.mark.parametrize("plant", [_state_unchanged, _half_batch, _answer_altered])
def test_broken_timed_path_is_not_correct(tiny, fresh_programs, monkeypatch, plant):
    plant(monkeypatch)
    res = run(tiny, monkeypatch)
    assert not res["correct"], res["checks"]


def test_reference_follows_the_documented_seed_streams():
    from reference import lsq_admm

    config = harness.resolve(CELL)["config"]
    case = dict(config["case"], seed=7, response="pareto", scheme="approx",
                deadline=3e-4, S=2)
    tr = lsq_admm.run(config, case, 30)
    assert tr["accuracy"][0] < 1.0 and np.all(np.diff(tr["sim_time"]) > 0)
    again = lsq_admm.run(config, case, 30)
    assert all(np.array_equal(tr[k], again[k]) for k in ("accuracy", "sim_time"))
