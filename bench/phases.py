"""Device idle time charged to the program's own phases.

The sweep engine writes host spans named ``repro.*`` around the layers of
one `run_sweep` call (`jax.profiler.TraceAnnotation`; see DESIGN.md
§16): the whole call, then per dispatch group (per chunk in the sharded
tier) materialize, prepare, stack, transfer and execute. They sit on the
profiler's host clock, the clock of the device planes, and their keyword
arguments (``runs``, ``bytes``) come back as event stats. Python writes
them on a host line named after its thread alone (``python``,
``python3``: the process's name), where the runtime's own threads are
named ``<thread>/<id>``.

At each instant of the traced window in which a device runs no op, the
innermost open program span names what the host was doing:

- inside ``repro.sweep.execute``, before the first device op that starts
  in it, the device waits for the host-to-device copy and relayout that
  the runtime finishes after ``jnp.asarray`` returns: **transfer**;
- inside ``repro.sweep.execute``, after its last device op: **execute**
  (results back to the host);
- everywhere else, the span's own name.

Where no program span is open, the idle time keeps the benchmark's own
label (`trace_reduce.label_gaps`). Like `trace_reduce`, everything below
the loader works on plain tuples.
"""

from __future__ import annotations

import bisect
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from trace_reduce import Event, Trace, gaps, label_gaps, summarize as base_summary

PREFIX = "repro."
RUNTIME_LINE = re.compile(r"/-?\d+$")  # ``<thread>/<id>``: not Python's
EXECUTE = "repro.sweep.execute"
TRANSFER = "repro.sweep.transfer"
SHARES = {
    "materialize_share.sweep": "repro.sweep.materialize",
    "prepare_share.sweep": "repro.sweep.prepare",
    "stack_share.sweep": "repro.sweep.stack",
    "transfer_share.sweep": TRANSFER,
}

Span = Tuple[str, int, int, Dict[str, int]]  # (name, start_ns, end_ns, stats)


def load(path) -> List[Span]:
    """The program spans of every ``.xplane.pb`` under ``path``, with
    their stats, read from Python's host lines alone (the runtime's own
    host threads can hold millions of events)."""
    from jax.profiler import ProfileData

    path = Path(path)
    files = [path] if path.is_file() else sorted(path.rglob("*.xplane.pb"))
    out: List[Span] = []
    for f in files:
        for plane in ProfileData.from_file(str(f)).planes:
            if plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                if RUNTIME_LINE.search(line.name):
                    continue
                out.extend(
                    (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
                     {k: int(v) for k, v in e.stats})
                    for e in line.events if e.name.startswith(PREFIX)
                )
    return sorted(out, key=lambda s: (s[1], -s[2]))


def pieces(spans: Sequence[Span], device: Sequence[Event], lo: int, hi: int
           ) -> List[Event]:
    """[lo, hi) cut at the program spans' edges (and, in each execute
    span, at its first device op's start and its last one's end), as
    sorted ``(phase, start, end)`` pieces; stretches in which no program
    span is open are left out."""
    starts = sorted((s, e) for _, s, e in device)
    first = [s for s, _ in starts]
    cuts = {lo, hi}
    marks = []  # per span: (first op start, last op end) for execute spans
    for name, s, e, _ in spans:
        cuts.update((s, e))
        mark = None
        if name == EXECUTE:
            inside = starts[bisect.bisect_left(first, s):bisect.bisect_left(first, e)]
            if inside:
                mark = (inside[0][0], min(max(b for _, b in inside), e))
                cuts.update(mark)
            else:
                mark = (e, e)  # no op ran: the whole span waited
        marks.append(mark)
    edges = sorted(c for c in cuts if lo <= c <= hi)
    out: List[Event] = []
    for a, b in zip(edges, edges[1:]):
        open_ = [i for i, (_, s, e, _) in enumerate(spans) if s <= a and b <= e]
        if not open_:
            continue
        # innermost: the latest start, then the earliest end
        i = max(open_, key=lambda j: (spans[j][1], -spans[j][2]))
        name = spans[i][0]
        if name == EXECUTE and b <= marks[i][0]:
            name = TRANSFER
        if out and out[-1][0] == name and out[-1][2] == a:
            out[-1] = (name, out[-1][1], b)
        else:
            out.append((name, a, b))
    return out


def cut(idle: Sequence[Tuple[int, int]], named: Sequence[Event]
        ) -> List[Tuple[Optional[str], int, int]]:
    """Idle stretches cut at the named pieces' edges, each piece named
    after the piece it lies in, or None outside every piece."""
    out: List[Tuple[Optional[str], int, int]] = []
    j = 0
    for a, b in idle:
        while j < len(named) and named[j][2] <= a:
            j += 1
        k, now = j, a
        while now < b:
            if k < len(named) and named[k][1] < b:
                name, s, e = named[k]
                if s > now:
                    out.append((None, now, s))
                    now = s
                end = min(e, b)
                out.append((name, now, end))
                now = end
                k += 1
            else:
                out.append((None, now, b))
                now = b
    return out


def summarize(trace: Trace, spans: Sequence[Span], top: int = 10) -> Optional[dict]:
    """`trace_reduce.summarize`, with the program's phases. Without
    program spans it is exactly that. With them, ``idle_by_phase`` holds
    the idle nanoseconds charged to each phase (averaged over devices, as
    the busy time is), and ``idle_gaps`` the longest idle stretches of the
    first device cut at the phases: pieces inside a program span carry
    its phase, the rest the benchmark's label."""
    s = base_summary(trace, top)
    if s is None or not spans:
        return s
    lo, hi = trace.window()
    devices = sorted(trace.ops)
    charged: Dict[str, float] = {}
    first = None
    for d in devices:
        ops = trace.ops[d]
        parts = cut(gaps(ops, lo, hi), pieces(spans, ops, lo, hi))
        for name, a, b in parts:
            if name is not None:
                charged[name] = charged.get(name, 0.0) + (b - a) / len(devices)
        first = parts if first is None else first
    longest = sorted(first, key=lambda p: p[1] - p[2])[:top]
    outside = iter(label_gaps([(a, b) for n, a, b in longest if n is None],
                              trace.spans, trace.ops[devices[0]]))
    idle = [(n, b - a) if n is not None else next(outside) for n, a, b in longest]
    return dict(s, idle_gaps=idle, idle_by_phase=charged)


def readings(trace: Trace, spans: Sequence[Span], summary: Optional[dict]
             ) -> Optional[Dict[str, float]]:
    """The phase shares of the traced window (%), and the host bytes sent
    per run: sum of ``bytes`` over sum of ``runs`` of the transfer spans
    that start in the window. None without program spans."""
    if summary is None or "idle_by_phase" not in summary:
        return None
    by_phase, window = summary["idle_by_phase"], summary["window_ns"]
    out = {k: 100.0 * by_phase.get(v, 0.0) / window for k, v in SHARES.items()}
    lo, hi = trace.window()
    sent = [st for name, s, _, st in spans if name == TRANSFER and lo <= s < hi]
    runs = sum(st.get("runs", 0) for st in sent)
    if runs:
        out["h2d_bytes_per_run.sweep"] = sum(st.get("bytes", 0) for st in sent) / runs
    return out
