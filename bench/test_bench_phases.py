"""Device idle time charged to the program's phases (``bench/phases.py``):
on synthetic event lists with known answers, and on the traces recorded on
one TPU v5e (``bench/testdata/small.xplane.pb``, recorded before the
program wrote spans, and ``bench/testdata/spans.xplane.pb``, one small
fleet job with the program's ``repro.*`` spans)."""

import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import phases  # noqa: E402
import trace_reduce as tr  # noqa: E402

SMALL = BENCH / "testdata" / "small.xplane.pb"
SPANS = BENCH / "testdata" / "spans.xplane.pb"
DEV = "/device:TPU:0"

# One job: materialize, prepare, stack and transfer before the first
# device op (at 500), two ops inside execute, results back at 880.
PROGRAM = [
    ("repro.sweep", 10, 900, {"runs": 6}),
    ("repro.sweep.materialize", 20, 100, {}),
    ("repro.sweep.prepare", 100, 300, {"runs": 6}),
    ("repro.sweep.stack", 300, 350, {}),
    ("repro.sweep.transfer", 350, 400, {"runs": 6, "bytes": 600}),
    ("repro.sweep.execute", 400, 880, {}),
]
OPS = [("fusion.1", 500, 600), ("fusion.2", 610, 800)]
BENCH_SPANS = [("bench.window", 0, 1000), ("bench.step", 0, 1000)]


def _trace(ops=None):
    return tr.Trace(ops=ops or {DEV: OPS}, modules={}, spans=BENCH_SPANS)


def test_pieces_name_the_innermost_span_and_split_execute():
    assert phases.pieces(PROGRAM, OPS, 0, 1000) == [
        ("repro.sweep", 10, 20),
        ("repro.sweep.materialize", 20, 100),
        ("repro.sweep.prepare", 100, 300),
        ("repro.sweep.stack", 300, 350),
        # the transfer span, then the wait for the first op in execute
        ("repro.sweep.transfer", 350, 500),
        # from the first op to the span's end, the tail included
        ("repro.sweep.execute", 500, 880),
        ("repro.sweep", 880, 900),
    ]


def test_execute_without_a_device_op_is_all_transfer_wait():
    spans = [("repro.sweep.execute", 100, 200, {})]
    assert phases.pieces(spans, [("op", 250, 260)], 0, 300) == [
        ("repro.sweep.transfer", 100, 200),
    ]


def test_pieces_are_clipped_to_the_window():
    assert phases.pieces(PROGRAM[:3], OPS, 50, 150) == [
        ("repro.sweep.materialize", 50, 100), ("repro.sweep.prepare", 100, 150),
    ]


def test_cut_splits_gaps_at_piece_edges():
    named = [("a", 10, 20), ("b", 20, 40), ("c", 50, 90)]
    idle = [(0, 15), (30, 60), (70, 80), (95, 99)]
    assert phases.cut(idle, named) == [
        (None, 0, 10), ("a", 10, 15),
        ("b", 30, 40), (None, 40, 50), ("c", 50, 60),
        ("c", 70, 80),
        (None, 95, 99),
    ]


def test_summary_charges_idle_time_and_names_the_gaps():
    s = phases.summarize(_trace(), PROGRAM)
    base = tr.summarize(_trace())
    assert {k: v for k, v in s.items() if k not in ("idle_gaps", "idle_by_phase")} == {
        k: v for k, v in base.items() if k != "idle_gaps"
    }
    assert s["idle_by_phase"] == {
        "repro.sweep": 30, "repro.sweep.materialize": 80,
        "repro.sweep.prepare": 200, "repro.sweep.stack": 50,
        "repro.sweep.transfer": 150, "repro.sweep.execute": 90,
    }
    # Outside every program span the benchmark's own label stays.
    assert s["idle_gaps"] == [
        ("repro.sweep.prepare", 200), ("repro.sweep.transfer", 150),
        ("bench.step.tail", 100), ("repro.sweep.materialize", 80),
        ("repro.sweep.execute", 80), ("repro.sweep.stack", 50),
        ("repro.sweep", 20), ("bench.step.lead", 10), ("repro.sweep", 10),
        ("repro.sweep.execute", 10),
    ]
    idle = s["window_ns"] - s["busy_ns"]
    assert sum(s["idle_by_phase"].values()) + 10 + 100 == idle


def test_summary_averages_the_charge_over_devices():
    other = [("fusion.1", 700, 800)]
    both = phases.summarize(_trace({DEV: OPS, "/device:TPU:1": other}), PROGRAM)
    one = phases.summarize(_trace({DEV: OPS}), PROGRAM)["idle_by_phase"]
    two = phases.summarize(_trace({DEV: other}), PROGRAM)["idle_by_phase"]
    assert two["repro.sweep.transfer"] == 350  # waits until 700
    for name in set(one) | set(two):
        assert both["idle_by_phase"][name] == pytest.approx(
            (one.get(name, 0) + two.get(name, 0)) / 2)


def test_summary_without_program_spans_is_the_benchmarks():
    assert phases.summarize(_trace(), []) == tr.summarize(_trace())
    small = tr.load(SMALL)
    assert phases.load(SMALL) == []
    assert phases.summarize(small, phases.load(SMALL)) == tr.summarize(small)


def test_readings_of_a_synthetic_trace():
    t = _trace()
    got = phases.readings(t, PROGRAM, phases.summarize(t, PROGRAM))
    assert got == {
        "materialize_share.sweep": pytest.approx(8.0),
        "prepare_share.sweep": pytest.approx(20.0),
        "stack_share.sweep": pytest.approx(5.0),
        "transfer_share.sweep": pytest.approx(15.0),
        "h2d_bytes_per_run.sweep": pytest.approx(100.0),
    }


def test_readings_find_nothing_without_program_spans():
    t = _trace()
    assert phases.readings(t, [], phases.summarize(t, [])) is None
    assert phases.readings(t, PROGRAM, None) is None


# -- the recorded chip trace with program spans ------------------------------

EXISTING = ("idle_share.sweep", "host_lead_share.sweep", "device_ns_per_run_iter.sweep",
            "coded_admm_update_roofline", "mfu.sweep")
# The recorded job: 3 runs x 10 iterations (one seed; lognormal; cyclic,
# mds, approx; S = 1). Host bytes a run: O, T, x_star, O_test, T_test,
# rho (float64), mu (int32), then 10 rows of the schedule and the clock.
RUNS, ITERS, BYTES_PER_RUN = 3, 10, 1_775_476


@pytest.fixture(scope="module")
def recorded():
    trace = tr.load(SPANS)
    spans = phases.load(SPANS)
    return trace, spans, phases.summarize(trace, spans)


def _run(trace, summary):
    calls = {"J": 6, "n": 3, "calls": RUNS * ITERS}
    return types.SimpleNamespace(
        trace=trace, summary=summary, window_s=summary["window_ns"] / 1e9, setup_s=1.0,
        counters={"runs": RUNS, "run_iters": RUNS * ITERS, "flops": 80250, "bytes": 94320,
                  "coded_admm_update": calls},
        peaks=harness.peaks("TPU v5 lite"),
    )


def test_recorded_spans_are_read_with_their_args(recorded):
    trace, spans, _ = recorded
    assert [s[0] for s in spans] == [
        "repro.sweep", "repro.sweep.materialize", "repro.sweep.prepare",
        "repro.sweep.stack", "repro.sweep.transfer", "repro.sweep.execute",
    ]
    stats = {name: st for name, _, _, st in spans}
    assert stats["repro.sweep"] == stats["repro.sweep.prepare"] == {"runs": RUNS}
    assert stats["repro.sweep.transfer"] == {"runs": RUNS, "bytes": RUNS * BYTES_PER_RUN}
    (step,) = trace.spans_named("bench.step")
    assert all(step[1] <= s <= e <= step[2] for _, s, e, _ in spans)
    # The benchmark's loader keeps its own spans only.
    assert {n for n, _, _ in trace.spans} == {"bench.window", "bench.step"}


def test_recorded_readings(recorded):
    trace, spans, summary = recorded
    got = phases.readings(trace, spans, summary)
    assert set(got) == set(phases.SHARES) | {"h2d_bytes_per_run.sweep"}
    assert got["h2d_bytes_per_run.sweep"] == BYTES_PER_RUN
    assert all(v > 0 for v in got.values())
    idle = 100 * (1 - summary["busy_ns"] / summary["window_ns"])
    assert sum(got[k] for k in phases.SHARES) <= idle
    assert any(n.startswith("repro.sweep.") for n, _ in summary["idle_gaps"])


def test_existing_readings_do_not_move_with_program_spans(recorded):
    trace, _, summary = recorded
    base = tr.summarize(trace)
    assert summary["device_ops"] == base["device_ops"]
    for name in EXISTING:
        reader = harness.load_module(BENCH / "metrics" / f"{name}.py", "reader")
        assert reader.read(_run(trace, summary)) == reader.read(_run(trace, base)) is not None
