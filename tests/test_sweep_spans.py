"""Host spans of the sweep engine (DESIGN.md §16).

`run_sweep` writes ``repro.sweep.*`` spans with `jax.profiler.TraceAnnotation`
at the layer boundaries of one call: the call, materialize, then per
dispatch group (per chunk in the sharded tier) prepare, stack, transfer
and execute. Each test records a real CPU profiler trace of a tiny sweep
(with the benchmark's profiler options: no Python tracer) and reads the
spans back with their keyword arguments, as the benchmark's trace
reduction does. conftest.py forces 8 CPU devices, so the sharded tier is
the real one.
"""

import jax
import pytest
from jax.profiler import ProfileData

from repro.experiments import Case, run_sweep
from repro.experiments.sweep import _materialize
from repro.methods import Reduction, driver, get_kernel

PHASES = ("repro.sweep.prepare", "repro.sweep.stack", "repro.sweep.transfer",
          "repro.sweep.execute")
RED = Reduction(fields=("accuracy",), budgets=(0.01,), x="sim_time")


def _cases(n):
    return [
        Case(method="csI-ADMM", dataset="usps", N=5, K=6, M=36, S=1,
             scheme="cyclic", iters=20, seed=s)
        for s in range(n)
    ]


def _record(tmp_path, **kw):
    """Run a sweep under the profiler; its ``repro.*`` spans, in order of
    start, as (name, start_ns, end_ns, stats)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        res = run_sweep(**kw)
    spans = []
    for f in tmp_path.rglob("*.xplane.pb"):
        for plane in ProfileData.from_file(str(f)).planes:
            for line in plane.lines:
                spans.extend(
                    (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
                     dict(e.stats))
                    for e in line.events if e.name.startswith("repro.")
                )
    return res, sorted(spans, key=lambda s: (s[1], -s[2]))


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _check_groups(spans, groups):
    """One repro.sweep span around everything; materialize first; then
    ``groups`` rounds of prepare -> stack -> transfer -> execute, each
    span ending before the next starts."""
    top, *rest = spans
    assert top[0] == "repro.sweep"
    assert all(_inside(s, top) for s in rest)
    assert rest[0][0] == "repro.sweep.materialize"
    names = [s[0] for s in rest[1:]]
    assert names == list(PHASES) * groups
    assert all(a[2] <= b[1] for a, b in zip(rest, rest[1:]))


def _stacked_nbytes(cases, clock):
    """Host bytes of the group's stacked inputs, rebuilt from the cases."""
    kernel = get_kernel(cases[0].method)
    mats = [_materialize(c, {}, {}) for c in cases]
    _, _, consts, steps = driver._stack_batch(
        kernel, [m[1] for m in mats], [m[0] for m in mats],
        [kernel.config(c) for c in cases], cases[0].iters, clock=clock,
    )
    return sum(a.nbytes for a in consts + steps)


@pytest.mark.parametrize("reduced", [True, False], ids=["summaries", "traces"])
def test_batched_tier_records_one_span_per_phase(tmp_path, reduced):
    cases = _cases(3)
    res, spans = _record(tmp_path, spec_or_cases=cases, mode="batched",
                         reductions=RED if reduced else None)
    assert res.n_dispatches == 1
    _check_groups(spans, groups=1)
    by_name = {s[0]: s[3] for s in spans}
    assert by_name["repro.sweep"] == {"runs": 3}
    assert by_name["repro.sweep.prepare"] == {"runs": 3}
    assert by_name["repro.sweep.transfer"] == {
        "runs": 3, "bytes": _stacked_nbytes(cases, clock=reduced),
    }
    for name in ("repro.sweep.materialize", "repro.sweep.stack",
                 "repro.sweep.execute"):
        assert by_name[name] == {}


def test_sharded_chunks_record_spans_per_chunk(tmp_path, monkeypatch):
    # A zero budget clamps every dispatch to the 8 devices: 9 runs go in
    # two chunks, the second padded to 8 rows.
    monkeypatch.setenv("REPRO_SHARD_MEM_MB", "0")
    cases = _cases(9)
    res, spans = _record(tmp_path, spec_or_cases=cases, mode="sharded",
                         reductions=RED)
    assert res.mode == "sharded" and len(jax.devices()) == 8
    top, materialize, probe, *chunks = spans
    assert (top[0], top[3]) == ("repro.sweep", {"runs": 9})
    assert materialize[0] == "repro.sweep.materialize"
    assert (probe[0], probe[3]) == ("repro.sweep.prepare", {"runs": 1})
    assert [s[0] for s in chunks] == list(PHASES) * 2
    assert all(_inside(s, top) for s in spans[1:])
    assert [s[3]["runs"] for s in chunks if s[0] == "repro.sweep.prepare"] == [8, 1]
    sent = [s[3] for s in chunks if s[0] == "repro.sweep.transfer"]
    assert [st["runs"] for st in sent] == [8, 8]
    per_run = _stacked_nbytes(cases[:1], clock=True)
    assert [st["bytes"] for st in sent] == [8 * per_run, 8 * per_run]


def test_sharded_trace_path_records_spans_per_chunk(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_SHARD_MEM_MB", "0")
    cases = _cases(9)
    _, spans = _record(tmp_path, spec_or_cases=cases, mode="sharded")
    names = [s[0] for s in spans]
    # prepare and stack of the whole group, then per chunk the padded
    # slice (stack), transfer and execute.
    assert names == [
        "repro.sweep", "repro.sweep.materialize", "repro.sweep.prepare",
        "repro.sweep.stack",
    ] + ["repro.sweep.stack", "repro.sweep.transfer", "repro.sweep.execute"] * 2
    sent = [s[3] for s in spans if s[0] == "repro.sweep.transfer"]
    per_run = _stacked_nbytes(cases[:1], clock=False)
    assert sent == [{"runs": 8, "bytes": 8 * per_run}] * 2


def test_serial_tier_records_no_driver_spans(tmp_path):
    cases = _cases(2)
    _, spans = _record(tmp_path, spec_or_cases=cases, mode="serial",
                       reductions=RED)
    assert [s[0] for s in spans] == ["repro.sweep", "repro.sweep.materialize"]

