"""Operation and byte counts of the benchmark's work (``bench/work.py``)
and the table of peaks, at small shapes."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import work  # noqa: E402


@pytest.mark.parametrize("J,n", [(1, 1), (3, 640), (6, 3), (16, 1 << 20)])
def test_coded_admm_update_counts_one_pass(J, n):
    flops, nbytes = work.coded_admm_update(J, n)
    # J message rows and x, y, z read once; x+ written once; 4-byte floats.
    assert nbytes == 4 * (J + 4) * n
    assert flops == 2 * J * n + 6 * n
    assert work.coded_admm_update(J, n, itemsize=2)[1] == nbytes // 2


def test_admm_run_iteration_by_hand():
    # N=2 agents, K=2 ECNs, mu=1 row, p=d=1 (n=1), one field, no summaries.
    flops, nbytes = work.admm_run_iteration(2, 2, 1, 1, 1, fields=1, summaries=0)
    grad = 2 * 1 * (4 + 1) + 2  # residual and O^T r per row, 1/mu scale
    combine = 2 * 2 + 6
    update = 9
    metrics = 2 * 5 + 2 + 4
    assert flops == grad + combine + update + metrics
    rows, state, xs, sched = 2 * 1 * 2 * 4, 6 * 4, 2 * 4, (2 * 2 + 6) * 4
    assert nbytes == rows + state + xs + sched


def test_admm_run_iteration_grows_with_the_mini_batch():
    small = work.admm_run_iteration(10, 6, 20, 3, 1, 2, 12)
    large = work.admm_run_iteration(10, 6, 30, 3, 1, 2, 12)
    assert large[0] - small[0] == 6 * 10 * (4 * 3 + 1)
    assert large[1] - small[1] == 6 * 10 * 4 * 4


def test_train_flops_per_token_matches_six_n_plus_attention():
    # One layer, d=4, 2 heads of 2 and 1 kv head, d_ff 8, vocab 10, seq 3.
    attn_params = 4 * 2 * (2 * 2 + 2 * 1)
    params = attn_params + 3 * 4 * 8 + 4 * 10
    got = work.train_flops_per_token(1, 4, 2, 1, 2, 8, 10, 3)
    assert got == 6 * params + 6 * 1 * 2 * 2 * 3


def test_train_flops_per_token_qwen3_0_6b_scale():
    # Qwen3-0.6B at seq 128: about 3.6 GFLOP per token, 6 N dominating.
    got = work.train_flops_per_token(28, 1024, 16, 8, 128, 3072, 151936, 128)
    assert 3.4e9 < got < 3.8e9


def test_peaks_table_names_its_source_and_v5e():
    table = json.loads((BENCH / "peaks.json").read_text())
    assert "TPU v5e" in table["source"]
    v5e = table["devices"]["TPU v5 lite"]
    assert v5e["flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
