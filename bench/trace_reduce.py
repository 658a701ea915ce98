"""From a profiler trace to device busy time, idle gaps and op times.

The profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``;
`jax.profiler.ProfileData` reads it. Device planes are named
``/device:<KIND>:<id>``; their ``XLA Ops`` line holds one event per
operation that ran, their ``XLA Modules`` line one per program. Host
threads carry the benchmark's own spans (``bench.*`` names, written with
`jax.profiler.TraceAnnotation`) on the same clock.

Everything below the loader works on plain ``(name, start_ns, end_ns)``
tuples, so it is tested without a trace file.
"""

from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]  # (name, start_ns, end_ns)


def op_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Trace:
    """Events of one traced window, grouped by where they ran."""

    ops: Dict[str, List[Event]]  # device plane name -> op events
    modules: Dict[str, List[Event]]  # device plane name -> program events
    spans: List[Event]  # the benchmark's host spans

    def window(self) -> Optional[Tuple[int, int]]:
        """The ``bench.window`` span, if it was recorded."""
        for name, lo, hi in self.spans:
            if name == "bench.window":
                return lo, hi
        return None

    def spans_named(self, name: str) -> List[Event]:
        return sorted(s for s in self.spans if s[0] == name)


def load(path, span_thread: Optional[int] = None) -> Trace:
    """Read every ``.xplane.pb`` under ``path`` (a file or a directory).

    Host lines are named ``<thread>/<id>``; with ``span_thread`` only that
    thread's line is searched for spans (the runtime's own host threads
    can hold millions of events), and every host line if it has none.
    """
    from jax.profiler import ProfileData

    path = Path(path)
    files = [path] if path.is_file() else sorted(path.rglob("*.xplane.pb"))
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host = []
    for f in files:
        data = ProfileData.from_file(str(f))
        for plane in data.planes:
            device = plane.name.startswith("/device:") and "CPU" not in plane.name
            for line in plane.lines:
                if device and line.name in ("XLA Ops", "XLA Modules"):
                    out = ops if line.name == "XLA Ops" else modules
                    out.setdefault(plane.name, []).extend(
                        (op_name(e.name), int(e.start_ns),
                         int(e.start_ns + e.duration_ns))
                        for e in line.events
                    )
                elif not device:
                    host.append(line)

    def spans_of(lines) -> List[Event]:
        return [
            (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for line in lines for e in line.events
            if e.name.startswith(SPAN_PREFIX)
        ]

    spans: List[Event] = []
    if span_thread is not None:
        spans = spans_of(ln for ln in host if ln.name.endswith(f"/{span_thread}"))
    if not spans:
        spans = spans_of(host)
    return Trace(ops=ops, modules=modules, spans=spans)


# -- interval algebra --------------------------------------------------------


def merge(intervals: Iterable[Tuple[int, int]], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Union of intervals clipped to [lo, hi), as sorted disjoint pieces."""
    out: List[Tuple[int, int]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_ns(events: Sequence[Event], lo: int, hi: int) -> int:
    """Nanoseconds of [lo, hi) in which at least one event ran."""
    return sum(b - a for a, b in merge(((s, e) for _, s, e in events), lo, hi))


def gaps(events: Sequence[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Stretches of [lo, hi) in which no event ran."""
    out, now = [], lo
    for a, b in merge(((s, e) for _, s, e in events), lo, hi):
        if a > now:
            out.append((now, a))
        now = b
    if hi > now:
        out.append((now, hi))
    return out


def time_by_name(events: Sequence[Event], lo: int, hi: int) -> Dict[str, int]:
    """Nanoseconds per event name, each event clipped to [lo, hi)."""
    out: Dict[str, int] = {}
    for name, s, e in events:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            out[name] = out.get(name, 0) + d
    return out


def first_start_in(events: Sequence[Event], lo: int, hi: int) -> Optional[int]:
    """Start of the first event that starts inside [lo, hi)."""
    starts = [s for _, s, _ in events if lo <= s < hi]
    return min(starts) if starts else None


def label_gaps(
    idle: Sequence[Tuple[int, int]], spans: Sequence[Event], device: Sequence[Event]
) -> List[Tuple[str, int]]:
    """Name each idle gap after the innermost benchmark span around its
    midpoint, suffixed ``.lead`` when it precedes that span's first device
    op, ``.tail`` when it follows its last and ``.mid`` otherwise."""
    out = []
    for a, b in idle:
        mid = (a + b) // 2
        around = [(s[1], -s[2], i) for i, s in enumerate(spans) if s[1] <= mid < s[2]]
        if not around:
            out.append(("outside_spans", b - a))
            continue
        name, s0, s1 = spans[max(around)[2]]  # latest start, then earliest end
        inside = [(s, e) for _, s, e in device if s0 <= s < s1]
        if not inside or mid < min(s for s, _ in inside):
            phase = "lead"
        elif mid >= max(e for _, e in inside):
            phase = "tail"
        else:
            phase = "mid"
        out.append((f"{name}.{phase}", b - a))
    return out


def summarize(trace: Trace, top: int = 10) -> Optional[dict]:
    """Busy and idle time of the traced window, averaged over devices:
    ``busy_ns`` is the union of op events, ``program_ns`` the union of
    program (module) events, the time the device spent in the steps'
    programs; with the costliest ops and the longest labelled idle gaps."""
    win = trace.window()
    if win is None or not trace.ops:
        return None
    lo, hi = win
    devices = sorted(trace.ops)
    busy = [busy_ns(trace.ops[d], lo, hi) for d in devices]
    if not any(busy):
        return None
    programs = [busy_ns(trace.modules.get(d) or trace.ops[d], lo, hi) for d in devices]
    first = trace.ops[devices[0]]
    by_name = time_by_name(first, lo, hi)
    longest = sorted(gaps(first, lo, hi), key=lambda g: g[0] - g[1])[:top]
    idle = label_gaps(longest, trace.spans, first)
    return {
        "window_ns": hi - lo,
        "busy_ns": sum(busy) / len(busy),
        "program_ns": sum(programs) / len(programs),
        "device_ops": sorted(by_name.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": idle,
    }
