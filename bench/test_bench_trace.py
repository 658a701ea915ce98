"""The trace reduction (``bench/trace_reduce.py``) and the readers of the
trace: on synthetic event lists with known answers, and on a small trace
recorded on one TPU v5e (``bench/testdata/small.xplane.pb``: two traced
jobs of 3 runs x 10 iterations of the fleet cell's step)."""

import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import trace_reduce as tr  # noqa: E402

SMALL = BENCH / "testdata" / "small.xplane.pb"


def test_union_of_overlapping_and_nested_events():
    ev = [("a", 0, 10), ("b", 5, 15), ("c", 20, 30), ("d", 22, 25), ("e", 40, 50)]
    assert tr.busy_ns(ev, 0, 60) == 15 + 10 + 10
    assert tr.merge([(s, e) for _, s, e in ev], 0, 60) == [(0, 15), (20, 30), (40, 50)]


def test_union_is_clipped_to_the_window():
    ev = [("a", -5, 5), ("b", 8, 12)]
    assert tr.busy_ns(ev, 0, 10) == 5 + 2
    assert tr.gaps(ev, 0, 10) == [(5, 8)]


def test_gaps_cover_the_window_with_the_busy_union():
    ev = [("a", 2, 4), ("b", 3, 6), ("c", 9, 10)]
    idle = tr.gaps(ev, 0, 12)
    assert idle == [(0, 2), (6, 9), (10, 12)]
    assert sum(b - a for a, b in idle) + tr.busy_ns(ev, 0, 12) == 12


def test_time_by_name_sums_clipped_durations():
    ev = [("k", 0, 4), ("k", 6, 8), ("m", 7, 20)]
    assert tr.time_by_name(ev, 1, 10) == {"k": 5, "m": 3}


def test_gaps_are_labelled_by_the_innermost_span_and_phase():
    spans = [("bench.window", 0, 100), ("bench.step", 0, 50), ("bench.step", 50, 100)]
    device = [("op", 30, 40), ("op", 42, 45), ("op", 80, 90)]
    # The gap 45..80 is named by its midpoint, in the second job.
    idle = tr.gaps(device, 0, 100)
    assert tr.label_gaps(idle, spans, device) == [
        ("bench.step.lead", 30), ("bench.step.mid", 2), ("bench.step.lead", 35),
        ("bench.step.tail", 10),
    ]


def test_op_names_drop_the_instruction_text():
    assert tr.op_name("%fusion.12 = f32[3]{0} fusion(f32[3]{0} %p)") == "fusion.12"
    assert tr.op_name("while.3") == "while.3"


def test_summary_of_a_synthetic_trace():
    t = tr.Trace(
        ops={"/device:TPU:0": [("k", 10, 30), ("j", 20, 40), ("k", 60, 70)]},
        modules={},
        spans=[("bench.window", 0, 100), ("bench.step", 0, 100)],
    )
    s = tr.summarize(t)
    assert s["window_ns"] == 100 and s["busy_ns"] == 40
    assert s["program_ns"] == 40  # no program events: the ops stand in
    assert s["device_ops"] == [("k", 30), ("j", 20)]
    assert s["idle_gaps"][0] == ("bench.step.tail", 30)


def test_program_time_is_the_union_of_program_events():
    t = tr.Trace(
        ops={"/device:TPU:0": [("k", 10, 30)]},
        modules={"/device:TPU:0": [("jit_a", 5, 35), ("jit_b", 30, 50), ("jit_c", 90, 120)]},
        spans=[("bench.window", 0, 100)],
    )
    assert tr.summarize(t)["program_ns"] == 45 + 10


def test_no_window_or_no_device_reads_nothing():
    assert tr.summarize(tr.Trace({}, {}, [("bench.window", 0, 9)])) is None
    assert tr.summarize(tr.Trace({"/device:TPU:0": [("a", 1, 2)]}, {}, [])) is None


@pytest.fixture(scope="module")
def small():
    return tr.load(SMALL)


def test_recorded_trace_has_the_device_and_the_spans(small):
    assert list(small.ops) == ["/device:TPU:0"]
    assert len(small.spans_named("bench.step")) == 2
    lo, hi = small.window()
    s = tr.summarize(small)
    assert s["window_ns"] == hi - lo
    assert 0 < s["busy_ns"] < s["window_ns"]
    assert any("coded_admm_update" in n for n, _, _ in small.ops["/device:TPU:0"])
    assert [m for m, _, _ in small.modules["/device:TPU:0"]][0].startswith("jit_run")


def _run(small, **counters):
    return types.SimpleNamespace(
        trace=small, summary=tr.summarize(small), window_s=0.0948, setup_s=1.0,
        counters=counters, peaks=harness.peaks("TPU v5 lite"),
    )


def test_readers_on_the_recorded_trace(small):
    calls = {"J": 6, "n": 3, "calls": 60}
    run = _run(small, runs=6, run_iters=60, flops=160500, bytes=188640,
               coded_admm_update=calls)
    read = {
        name: harness.load_module(BENCH / "metrics" / f"{name}.py", "reader").read(run)
        for name in ("idle_share.sweep", "host_lead_share.sweep",
                     "device_ns_per_run_iter.sweep", "coded_admm_update_roofline",
                     "mfu.sweep")
    }
    s = run.summary
    assert read["idle_share.sweep"] == pytest.approx(100 * (1 - s["busy_ns"] / s["window_ns"]))
    assert 0 < read["host_lead_share.sweep"] < 100
    assert read["device_ns_per_run_iter.sweep"] > 0
    assert 0 < read["coded_admm_update_roofline"] < 100
    assert 0 < read["mfu.sweep"] < 100


def test_readers_find_nothing_without_a_trace():
    run = types.SimpleNamespace(trace=None, summary=None, window_s=1.0, setup_s=1.0,
                                counters={}, peaks={})
    for name in ("idle_share.sweep", "host_lead_share.sweep",
                 "device_ns_per_run_iter.sweep", "coded_admm_update_roofline",
                 "mfu.sweep", "sweep_run_iters_per_s", "idle_share.train",
                 "device_ms_per_step.train", "mfu.train", "train_tokens_per_s"):
        assert harness.load_module(BENCH / "metrics" / f"{name}.py", "r").read(run) is None


@pytest.mark.parametrize("thread", [1368, 1, None])
def test_spans_are_read_from_the_harness_thread_or_all(small, thread):
    # The recorded run's harness thread is ``main/1368``; another id finds
    # no spans there and falls back to every host line.
    assert tr.load(SMALL, span_thread=thread).spans == small.spans


def test_train_readers_on_a_synthetic_trace():
    ms = 1_000_000
    t = tr.Trace(
        ops={"/device:TPU:0": [("fusion.1", 0, 400 * ms), ("fusion.2", 500 * ms, 900 * ms)]},
        modules={"/device:TPU:0": [("jit_train_step", 0, 450 * ms),
                                   ("jit_train_step", 500 * ms, 950 * ms)]},
        spans=[("bench.window", 0, 1000 * ms), ("bench.step", 0, 10 * ms),
               ("bench.step", 10 * ms, 20 * ms)],
    )
    peaks = harness.peaks("TPU v5 lite")
    run = types.SimpleNamespace(
        trace=t, summary=tr.summarize(t), window_s=1.0, setup_s=1.0, peaks=peaks,
        counters={"steps": 2, "tokens": 2048, "flops": 0.25 * peaks["flops_per_s"] * 0.9},
    )
    read = {
        name: harness.load_module(BENCH / "metrics" / f"{name}.py", "reader").read(run)
        for name in ("idle_share.train", "device_ms_per_step.train", "mfu.train")
    }
    assert read["idle_share.train"] == pytest.approx(20.0)
    assert read["device_ms_per_step.train"] == pytest.approx(450.0)
    assert read["mfu.train"] == pytest.approx(25.0)
