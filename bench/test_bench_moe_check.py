"""The check that decides ``correct`` for the cell
``moonlight-16b-a3b.consensus``, on the CPU at a size a test run holds:
the configuration's blocks at width 64 (MLA with a latent of 32, rope 16,
nope and v 32, 16 experts of width 32 with 4 held, top 4, one shared
expert), one dense and two expert layers, a vocabulary of 512 and 16
tokens a row, driven through the harness with the look for a chip
skipped. A sound run passes; the plain reference with its state in
float8 put in the program's place (the control) fails, as does the
reference with a capacity of 1.0 per held expert (the dropped-token
fault), the reference with half of each agent's rows left out (the
half-batch fault) and a step that returns its state unchanged. The counters and the
work counts (``bench/work_moe.py``) are checked by hand. The cell, its
configuration and its metrics are read from the repository's
``BENCHMARK.json``, as a run on the chip reads them."""

import copy
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402
import work_moe  # noqa: E402

CELL = "moonlight-16b-a3b.consensus"
SEED = 2**31 + 29


@pytest.fixture(scope="module")
def tiny():
    config = copy.deepcopy(harness.resolve(CELL)["config"])
    config.update(
        hidden_size=64, intermediate_size=128, kv_lora_rank=32, qk_nope_head_dim=32,
        qk_rope_head_dim=16, v_head_dim=32, num_attention_heads=4, num_key_value_heads=4,
        moe_intermediate_size=32, n_routed_experts=16, num_experts_per_tok=4,
        n_shared_experts=1,
    )
    config["held"] = {"layers": 3, "experts": 4, "expert_offset": 0, "vocab": 512}
    config["seq_len"] = 16
    return config


def run(config, monkeypatch):
    """One run of the cell through the harness on this host's first
    device, with the tiny configuration in place of the cell's."""
    import jax

    resolve, peaks = harness.resolve, harness.peaks
    monkeypatch.setattr(harness, "resolve", lambda cell, _=None: dict(resolve(cell), config=config))
    monkeypatch.setattr(harness, "devices", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "peaks", lambda kind: peaks("TPU v5 lite"))
    return harness.run_cell(CELL, SEED, 0.0, False, t_start=time.perf_counter(),
                            log=lambda msg: None)


def test_sound_run_is_correct(tiny, monkeypatch):
    res = run(tiny, monkeypatch)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}


@pytest.mark.parametrize(
    "side", ["control float8_e4m3fn", "fault dropped_tokens", "fault half_batch"])
def test_control_and_dropped_token_fault_are_not_correct(tiny, side):
    r = harness.resolve(CELL)
    workload = r["generator"].Workload(tiny, r["traffic"], SEED)
    kind, name = side.split()
    checks = workload.control(name) if kind == "control" else workload.fault(name)
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


def test_state_left_unchanged_is_not_correct(tiny, monkeypatch):
    from repro.distributed import ConsensusRuntime

    step = ConsensusRuntime.train_step

    def unchanged(self, state, batch, alive):
        return state, step(self, state, batch, alive)[1]

    monkeypatch.setattr(ConsensusRuntime, "train_step", unchanged)
    res = run(tiny, monkeypatch)
    assert not res["correct"], res["checks"]


def test_counters_sum_the_steps_routing(tiny):
    r = harness.resolve(CELL)
    workload = r["generator"].Workload(tiny, r["traffic"], SEED)
    workload.warm_up()
    records = [workload.step(i) for i in range(2)]
    c = workload.counters(records)
    m = workload.model
    # 2 steps x 2 agents x 8 rows, top 4, 2 expert layers
    slots = 2 * 2 * 8 * tiny["seq_len"] * m["num_experts_per_tok"] * (m["layers_held"] - 1)
    assert c["moe/dropped"] == 0
    assert 0 < c["moe/committed_rows"] < c["moe/held_rows"] < slots
    assert 0 < c["moe/max_rows"] <= c["moe/held_rows"]
    tokens = 2 * 8 * tiny["seq_len"]
    assert c["tokens"] == tokens
    assert c["flops"] == (tokens * work_moe.train_flops_per_token(m, tiny["seq_len"])
                          + work_moe.routed_flops(m, c["moe/committed_rows"]))
    assert c["expert_gmm"] == dict(zip(("flops", "bytes"), work_moe.expert_gmm_step(
        m, c["moe/held_rows"], passes=2 * 2 * 2, forward_runs=2)))


def test_work_counts_by_hand():
    # Width 4, 2 heads, latent 2, nope 1, rope 2, v 1; 1 dense + 1 expert
    # layer; 4 experts of width 3 (2 held), 1 shared; vocabulary 5.
    m = {"hidden_size": 4, "num_attention_heads": 2, "kv_lora_rank": 2,
         "qk_nope_head_dim": 1, "qk_rope_head_dim": 2, "v_head_dim": 1,
         "first_k_dense_replace": 1, "layers_held": 2, "intermediate_size": 6,
         "n_routed_experts": 4, "experts_held": 2, "moe_intermediate_size": 3,
         "n_shared_experts": 1, "vocab_held": 5}
    mla = 4 * 2 * 3 + 4 * (2 + 2) + 2 * 2 * 2 + 2 * 1 * 4
    assert work_moe.mla_params(m) == mla
    params = 2 * mla + 3 * 4 * 6 + (3 * 4 * 3 + 4 * 4) + 4 * 5
    assert work_moe.train_flops_per_token(m, seq=7) == 6 * params + 3 * 2 * 2 * (1 + 2 + 1) * 7
    assert work_moe.routed_flops(m, rows=10) == 6 * 3 * 4 * 3 * 10
    # 10 rows over 4 passes, forward recomputed: 3 * 2 + 6 = 12 products.
    flops, nbytes = work_moe.expert_gmm_step(m, rows=10, passes=4, forward_runs=2)
    assert flops == 2 * 4 * 3 * 10 * 12
    assert nbytes == 2 * ((4 + 3) * 10 * 12 + 2 * 4 * 3 * 4 * 12)


def test_moonlight_cut_counts_about_1_7_gflop_a_token():
    from reference import moonlight

    m = moonlight.dims(harness.resolve(CELL)["config"])
    per_token = work_moe.train_flops_per_token(m, 1024)
    # 6 x 249.6M matrix parameters touched + causal MLA scores and values.
    assert 1.55e9 < per_token < 1.6e9
    # Expected routed share: 6 of 64 slots a token land on the 8 held experts.
    routed = work_moe.routed_flops(m, rows=4 * 6 * 8 // 64)
    assert 1.5e8 < routed < 1.6e8
