"""The check that decides ``correct`` for the consensus-training cell, on
the CPU at a size a test run holds: Qwen3's shape with 2 layers of width
64, a vocabulary of 512 and 16 tokens a row, driven through the harness
with the look for a chip skipped. A sound run passes; the plain reference
with its state in float8 put in the program's place (the control) fails;
and so does a run with the timed path broken underneath: a step that
returns its state unchanged, half of the batch left out with the mean
taken over the rest, and the loss altered where the step produces it.
The cell runs on one chip, so no exchange between chips can be left
out. The cell, its configuration and its metrics are read from the
repository's ``BENCHMARK.json``, as a run on the chip reads them."""

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

CELL = "qwen3-0.6b.consensus"
SEED = 2**31 + 13


@pytest.fixture(scope="module")
def root():
    """The checkout whose ``BENCHMARK.json`` registers the cell."""
    return harness.ROOT


@pytest.fixture(scope="module")
def tiny(root):
    config = copy.deepcopy(harness.resolve(CELL, root)["config"])
    config["model"].update(
        hidden_size=64, intermediate_size=128, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16, vocab_size=512,
    )
    config["seq_len"] = 16
    return config


def run(config, root, monkeypatch):
    """One run of the cell through the harness on this host's first
    device, with the tiny configuration in place of the cell's."""
    import jax

    resolve, peaks = harness.resolve, harness.peaks

    def tiny_resolve(cell, _=None):
        return dict(resolve(cell, root), config=config)

    monkeypatch.setattr(harness, "resolve", tiny_resolve)
    monkeypatch.setattr(harness, "devices", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "peaks", lambda kind: peaks("TPU v5 lite"))
    return harness.run_cell(CELL, SEED, 0.0, False, t_start=time.perf_counter(),
                            log=lambda msg: None)


def test_sound_run_is_correct(tiny, root, monkeypatch):
    res = run(tiny, root, monkeypatch)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"


def test_control_in_float8_is_not_correct(tiny, root):
    r = harness.resolve(CELL, root)
    checks = r["generator"].Workload(tiny, r["traffic"], SEED).control("float8_e4m3fn")
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


def _state_unchanged(monkeypatch):
    from repro.distributed import ConsensusRuntime

    step = ConsensusRuntime.train_step

    def unchanged(self, state, batch, alive):
        return state, step(self, state, batch, alive)[1]

    monkeypatch.setattr(ConsensusRuntime, "train_step", unchanged)


def _half_batch(monkeypatch):
    from repro.distributed import ConsensusRuntime

    weights = ConsensusRuntime.row_weights

    def half(self, alive, rows_per_agent):
        K = self.cfg.K
        P = rows_per_agent // (K * (self.cfg.S + 1))
        part = np.repeat(np.asarray(self.support).reshape(-1), P)
        return weights(self, alive, rows_per_agent) * (2.0 * (part < K // 2))

    monkeypatch.setattr(ConsensusRuntime, "row_weights", half)


def _loss_altered(monkeypatch):
    from repro.distributed import ConsensusRuntime

    step = ConsensusRuntime.train_step

    def altered(self, state, batch, alive):
        new, metrics = step(self, state, batch, alive)
        return new, dict(metrics, loss=metrics["loss"] * 1.01)

    monkeypatch.setattr(ConsensusRuntime, "train_step", altered)


@pytest.mark.parametrize("plant", [_state_unchanged, _half_batch, _loss_altered])
def test_broken_timed_path_is_not_correct(tiny, root, monkeypatch, plant):
    plant(monkeypatch)
    res = run(tiny, root, monkeypatch)
    assert not res["correct"], res["checks"]


def test_reference_half_batch_fault_is_not_correct(tiny, root):
    r = harness.resolve(CELL, root)
    checks = r["generator"].Workload(tiny, r["traffic"], SEED).fault("half_batch")
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


def test_coded_feed_is_the_same_for_a_seed_and_covers_each_partition(tiny, root):
    r = harness.resolve(CELL, root)
    a = r["generator"].Workload(tiny, r["traffic"], SEED).batch()
    b = r["generator"].Workload(tiny, r["traffic"], SEED).batch()
    assert all(np.array_equal(a[0][k], b[0][k]) for k in a[0])
    assert np.array_equal(a[1], b[1]) and (a[1].sum(axis=1) == 3).all()
    # Each partition sits on S + 1 = 2 ECNs: every row appears twice.
    rows = a[0]["tokens"][:8]
    assert len({r.tobytes() for r in rows}) == 4
    assert np.array_equal(a[0]["labels"][:, :-1], a[0]["tokens"][:, 1:])
