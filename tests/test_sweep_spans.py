"""Host spans of the sweep engine (DESIGN.md §16).

`run_sweep` writes ``repro.sweep.*`` spans with `jax.profiler.TraceAnnotation`
at the layer boundaries of one call: the call, materialize, then per
dispatch chunk prepare, stack, transfer and execute (a group is one
chunk unless it outgrows the per-device memory budget). Each test records a real CPU profiler trace of a tiny sweep
(with the benchmark's profiler options: no Python tracer) and reads the
spans back with their keyword arguments, as the benchmark's trace
reduction does. conftest.py forces 8 CPU devices, so the sharded tier is
the real one.
"""

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.admm import make_schedule
from repro.core.coding import make_code
from repro.experiments import Case, run_sweep
from repro.experiments.sweep import _materialize
from repro.methods import Reduction, driver, get_kernel

PHASES = ("repro.sweep.prepare", "repro.sweep.stack", "repro.sweep.transfer",
          "repro.sweep.execute")
RED = Reduction(fields=("accuracy",), budgets=(0.01,), x="sim_time")


def _cases(n, share=1, scheme="cyclic", S=1):
    """``n`` runs; each ``share`` consecutive runs hold one seed's problem
    (they differ in rho)."""
    return [
        Case(method="csI-ADMM", dataset="usps", N=5, K=6, M=36, S=S,
             scheme=scheme, iters=20, seed=i // share,
             rho=1.0 + i % share)
        for i in range(n)
    ]


def _patterns(cases):
    """Distinct alive rows of the runs' schedules, summed: the decodes
    their prepare solves (none for an uncoded run)."""
    total = 0
    for case in cases:
        net, prob = _materialize(case, {}, {})
        run = get_kernel(case.method).config(case)
        if run.cfg.scheme != "uncoded":
            code = make_code(run.cfg.scheme, run.cfg.K, run.cfg.S,
                             seed=run.cfg.seed)
            sched = make_schedule(run.cfg, net, code, run.timing,
                                  case.iters, prob.b)
            total += len(np.unique(sched["alive"], axis=0))
    return total


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


def _stacked(cases, clock, copies=1):
    """The group's stacked inputs, rebuilt from the cases with the
    sweep's caches, so runs of one seed share its problem's arrays."""
    kernel = get_kernel(cases[0].method)
    nets, probs = {}, {}
    mats = [_materialize(c, nets, probs) for c in cases]
    preps = [
        kernel.prepare(p, n, kernel.config(c), c.iters)
        for c, (n, p) in zip(cases, mats)
    ]
    return driver._stack_runs(preps, clock, copies)


def _budget(chunk, case, clock):
    """A memory budget that holds ``chunk`` runs like ``case`` on each of
    the 8 devices, so the sharded tier dispatches 8 x ``chunk`` runs."""
    kernel = get_kernel(case.method)
    net, prob = _materialize(case, {}, {})
    prep = kernel.prepare(prob, net, kernel.config(case), case.iters)
    return 2 * chunk * driver._run_bytes(prep, clock)


def _nbytes(arrays):
    return sum(a.nbytes for a in jax.tree.leaves(arrays))


@pytest.mark.parametrize("share,tables", [(1, 3), (2, 2)],
                         ids=["own-data", "shared-data"])
@pytest.mark.parametrize("reduced", [True, False], ids=["summaries", "traces"])
def test_batched_tier_records_one_span_per_phase(tmp_path, reduced, share,
                                                 tables):
    # share=2: runs 0 and 1 hold one problem, so 2 tables for 3 runs.
    cases = _cases(3, share)
    res, spans = _record(tmp_path, spec_or_cases=cases, mode="batched",
                         reductions=RED if reduced else None)
    assert res.n_dispatches == 1
    _check_groups(spans, groups=1)
    by_name = {s[0]: s[3] for s in spans}
    assert by_name["repro.sweep"] == {"runs": 3}
    assert by_name["repro.sweep.prepare"] == {
        "runs": 3, "decode_patterns": _patterns(cases),
    }
    assert _patterns(cases) > 3
    batch = _stacked(cases, clock=reduced)
    assert by_name["repro.sweep.transfer"] == {
        "runs": 3, "tables": tables,
        "bytes": _nbytes((batch.tables, batch.per_run)),
    }
    # Each table ships once: only the per-run inputs grow with the runs.
    assert [len(t) for t in batch.tables] == (
        [] if tables == 3 else [tables] * 4
    )
    for name in ("repro.sweep.materialize", "repro.sweep.stack",
                 "repro.sweep.execute"):
        assert by_name[name] == {}


@pytest.mark.parametrize("share", [1, 32], ids=["own-data", "shared-data"])
def test_sharded_chunks_record_spans_per_chunk(tmp_path, monkeypatch, share):
    # own-data: a zero budget clamps every dispatch to the 8 devices, so 9
    # runs go in two chunks, the second padded to 8 rows. shared-data: 32
    # runs of one problem in two chunks of 16.
    cases = _cases(9 if share == 1 else 32, share)
    chunk = 8 if share == 1 else 16
    budget = 0 if share == 1 else _budget(2, cases[0], clock=True)
    monkeypatch.setattr(driver, "_MEM_BUDGET", budget)
    res, spans = _record(tmp_path, spec_or_cases=cases, mode="sharded",
                         reductions=RED)
    assert res.mode == "sharded" and len(jax.devices()) == 8
    # Each run is prepared once, inside its chunk's prepare span.
    _check_groups(spans, groups=2)
    assert (spans[0][0], spans[0][3]) == ("repro.sweep", {"runs": len(cases)})
    assert [s[3] for s in spans if s[0] == "repro.sweep.prepare"] == [
        {"runs": chunk, "decode_patterns": _patterns(cases[:chunk])},
        {"runs": len(cases) - chunk,
         "decode_patterns": _patterns(cases[chunk:])},
    ]
    sent = [s[3] for s in spans if s[0] == "repro.sweep.transfer"]
    assert [st["runs"] for st in sent] == [chunk, chunk]
    one = _stacked(cases[:1], clock=True)
    per_run = _nbytes((one.consts, one.steps))
    if share == 1:
        # Every run stacks its own data.
        assert [st["tables"] for st in sent] == [8, 8]
        assert [st["bytes"] for st in sent] == [8 * per_run, 8 * per_run]
    else:
        # The first chunk ships the one problem as a table, which the
        # second reuses: each ships its runs' index and their own consts.
        first = _stacked(cases[:chunk], clock=True, copies=8)
        assert first.index is not None
        assert [st["tables"] for st in sent] == [1, 0]
        assert [st["bytes"] for st in sent] == [
            _nbytes((first.tables, first.per_run)), _nbytes(first.per_run),
        ]


@pytest.mark.parametrize("share", [1, 3, 9],
                         ids=["own-data", "few-sharers", "shared-data"])
def test_sharded_trace_path_records_spans_per_chunk(tmp_path, monkeypatch,
                                                    share):
    monkeypatch.setattr(driver, "_MEM_BUDGET", 0)
    cases = _cases(9, share)
    _, spans = _record(tmp_path, spec_or_cases=cases, mode="sharded")
    # Per chunk of the 8 devices: prepare, stack, transfer and execute.
    _check_groups(spans, groups=2)
    sent = [s[3] for s in spans if s[0] == "repro.sweep.transfer"]
    first = _stacked(cases[:8], clock=False, copies=8)
    per_run = _nbytes(first.per_run) // 8
    # 8 problems, 3, or one that replicated on 8 devices would ship at
    # least 8 rows against 8 runs: every run ships its own data.
    assert first.index is None
    assert sent == [{"runs": 8, "tables": 8, "bytes": 8 * per_run}] * 2


def test_uncoded_job_solves_no_decode_patterns(tmp_path):
    cases = _cases(2, scheme="uncoded", S=0)
    _, spans = _record(tmp_path, spec_or_cases=cases, mode="batched",
                       reductions=RED)
    _check_groups(spans, groups=1)
    (prep,) = [s[3] for s in spans if s[0] == "repro.sweep.prepare"]
    assert prep == {"runs": 2, "decode_patterns": 0}


def test_serial_tier_records_no_driver_spans(tmp_path):
    cases = _cases(2)
    _, spans = _record(tmp_path, spec_or_cases=cases, mode="serial",
                       reductions=RED)
    assert [s[0] for s in spans] == ["repro.sweep", "repro.sweep.materialize"]

