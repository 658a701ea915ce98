"""The harness finds each cell's configuration, traffic and metric files
by name from ``BENCHMARK.json``, refuses unknown names, and refuses to
run without a TPU. Nothing here describes a TPU or loads its library."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_are_found_by_name(cell):
    r = harness.resolve(cell)
    assert r["cell"]["name"] == cell
    assert r["config"]["name"] == r["cell"]["config"]
    assert r["traffic"]["generator"]
    assert hasattr(r["generator"], "Workload")
    names = [m["name"] for m in r["end_to_end"] + r["per_layer"]]
    assert "setup_s" in names and len(r["end_to_end"]) >= 2 and r["per_layer"]
    for name in names:
        assert callable(r["readers"][name].read)


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_gets_exactly_the_metrics_that_name_it(cell):
    r = harness.resolve(cell)
    # A metric without a list of workloads belongs to every cell that
    # reports the end-to-end metric it moves.
    named = {m["name"] for m in SPEC["end_to_end"] if cell in m.get("workloads", [cell])}
    named |= {m["name"] for m in SPEC["per_layer"]
              if cell in m.get("workloads", [cell] if m["moves"] in named else [])}
    got = {m["name"] for m in r["end_to_end"] + r["per_layer"]}
    assert got == named == set(r["readers"])
    # A sweep cell and a training cell never read each other's metrics.
    kinds = {n.rsplit(".", 1)[1] for n in got if "." in n}
    assert len(kinds) <= 1
    for rate, kind in [("sweep_run_iters_per_s", "sweep"), ("train_tokens_per_s", "train")]:
        if rate in got:
            assert kinds == {kind}
    w = r["cell"]
    (config,) = [c for c in SPEC["configs"] if c["name"] == w["config"]]
    files = [ROOT / config["file"], BENCH / "traffic" / f"{w['traffic']}.json",
             BENCH / "generators" / f"{r['traffic']['generator']}.py"]
    files += [BENCH / "metrics" / f"{n}.py" for n in got]
    assert all(f.is_file() for f in files)


@pytest.mark.parametrize("cell", CELLS)
def test_every_per_layer_metric_moves_a_metric_its_cells_report(cell):
    r = harness.resolve(cell)
    reported = {m["name"] for m in r["end_to_end"]}
    assert all(m["moves"] in reported for m in r["per_layer"])


def test_names_units_and_files_keep_to_the_contract():
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    for entry in SPEC["configs"] + SPEC["workloads"] + metrics:
        assert NAME.match(entry["name"]), entry["name"]
    for m in metrics:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
    assert all(0 < m["bound"] <= 0.25 for m in SPEC["end_to_end"])
    assert {len(w["why"]) <= 200 for w in SPEC["workloads"]} == {True}


def test_unknown_cell_is_an_error():
    with pytest.raises(harness.BenchError, match="unknown cell"):
        harness.resolve("no_such.cell")


def test_unknown_metric_is_an_error(tmp_path):
    spec = json.loads(json.dumps(SPEC))
    spec["per_layer"].append(dict(spec["per_layer"][0], name="no_such_metric"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp_path / "bench").symlink_to(BENCH)
    with pytest.raises(harness.BenchError, match="no metric reader"):
        harness.resolve(CELLS[0], root=tmp_path)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.BenchError, match="no peaks"):
        harness.peaks("some other chip")


def test_command_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELLS[0],
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=120,
    )
    assert p.returncode == 2
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""
