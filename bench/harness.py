"""One run of one benchmark cell, found by name in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix. Everything that belongs
to one of them, or to one metric, sits in a file of its own that is found
by name:

- ``bench/configs/<config>.json``: the deployment as it is run;
- ``bench/traffic/<cell>.json``: the traffic, read by the general
  generator ``bench/generators/<generator>.py`` that the file names;
- ``bench/metrics/<metric>.py``: ``read(run)`` gives the metric's value
  from the run's host clocks, counters and trace, or None where it finds
  nothing to read.

A generator's ``Workload`` offers ``warm_up()``, ``step(i)`` (one unit
of closed-loop work; returns a record), ``finish()`` (waits until every
step's results are on the device or the host), ``counters(records)``
(the totals that the metric readers use), ``failed(records)``,
``check(records)`` (each compared number with its limit) and
``control(dtype)`` (the same numbers with the reference in a lower
precision in the program's place).

Set-up runs from the start of the process to the start of the window and
includes the warm-up; the window runs ``step`` until ``seconds`` have
passed (at least once) and counts every step it started, to its end.
Each step runs in a ``bench.step`` span, which the trace shows.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import types
from pathlib import Path
from typing import List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"


class BenchError(Exception):
    """The run cannot produce a result (no chip, unknown name, ...)."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, what: str):
    if not path.is_file():
        raise BenchError(f"no {what} at {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(cell: str, root: Path = ROOT) -> dict:
    """The cell's configuration, traffic, metrics and their readers."""
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if cell not in cells:
        raise BenchError(f"unknown cell {cell!r}; known: {sorted(cells)}")
    w = cells[cell]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in spec["end_to_end"] if cell in m.get("workloads", [cell])]
    moved = {m["name"] for m in e2e}
    per_layer = [
        m for m in spec["per_layer"]
        if (cell in m["workloads"] if "workloads" in m else m["moves"] in moved)
    ]
    readers = {
        m["name"]: load_module(root / "bench" / "metrics" / f"{m['name']}.py", "metric reader")
        for m in e2e + per_layer
    }
    generator = load_module(
        root / "bench" / "generators" / f"{traffic['generator']}.py", "generator"
    )
    return dict(cell=w, config=config, traffic=traffic, end_to_end=e2e,
                per_layer=per_layer, readers=readers, generator=generator)


def devices(chips: int) -> list:
    """The cell's chips; a run without enough TPUs has no result."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (first device: {devs[0].platform}); "
                         "this benchmark measures the chip only")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def peaks(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


class CompileCounter:
    """Programs compiled or loaded from the persistent cache, and misses."""

    def __init__(self):
        import jax

        self.programs = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def use_compile_cache() -> None:
    """JAX's persistent cache at a fixed directory inside the checkout;
    the program defers to the variable."""
    CACHE_DIR.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))


def run_cell(
    cell: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t_start: float,
    log=lambda msg: print(msg, file=sys.stderr, flush=True),
) -> dict:
    """Set up, measure, check; returns the result line as a dict.

    With ``trace`` the profiler records the window's first
    ``traced_steps`` steps (the traffic file's number), in a
    ``bench.window`` span that ends when their results are on the host;
    the rest of the window runs untraced. The per-layer metrics read those
    steps alone.
    """
    r = resolve(cell)
    import jax

    devs = devices(r["cell"]["chips"])
    dev = devs[0]
    peak = peaks(dev.device_kind)
    sys.path.insert(0, str(ROOT / "src"))
    counter = CompileCounter()
    workload = r["generator"].Workload(r["config"], r["traffic"], seed)
    t_warm = time.perf_counter()
    workload.warm_up()
    log(f"set-up: to the warm-up {t_warm - t_start:.3f} s, warm-up "
        f"{time.perf_counter() - t_warm:.3f} s, programs obtained {counter.programs} "
        f"(persistent cache misses {counter.misses})")
    n_traced = r["traffic"]["traced_steps"] if trace else 0
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        # Device ops and the benchmark's spans only: the Python tracer
        # would record every host call of the sweep and slow it.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tmp, profiler_options=opts)
    counter.programs = counter.misses = 0
    records: List[dict] = []
    step_s: List[float] = []

    def step():
        t_step = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            records.append(workload.step(len(records)))
        step_s.append(time.perf_counter() - t_step)

    t0 = time.perf_counter()
    setup_s = t0 - t_start
    if trace:
        with jax.profiler.TraceAnnotation("bench.window"):
            while len(records) < n_traced:
                step()
            workload.finish()
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        log(f"trace: {n_traced} steps in {t_stop - t0:.3f} s, written in "
            f"{time.perf_counter() - t_stop:.3f} s")
    while not records or time.perf_counter() - t0 < seconds:
        step()
    workload.finish()
    window_s = time.perf_counter() - t0
    compiles = (counter.programs, counter.misses)
    summary = tr = None
    if trace:
        import trace_reduce

        t_load = time.perf_counter()
        tr = trace_reduce.load(tmp, span_thread=threading.get_native_id())
        summary = trace_reduce.summarize(tr)
        log(f"trace: read and reduced in {time.perf_counter() - t_load:.3f} s")
        shutil.rmtree(tmp, ignore_errors=True)
    stats = [d.memory_stats() or {} for d in devs]
    memory_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    counters = workload.counters(records)
    log(f"steps took {[round(t, 3) for t in step_s]} s")
    log(f"setup_s {setup_s:.3f} window_s {window_s:.3f} steps {len(records)} "
        f"counters {json.dumps({k: v for k, v in counters.items() if not isinstance(v, dict)})}")
    log(f"compiles_in_window {compiles[0]} (persistent cache misses {compiles[1]})")

    failed = workload.failed(records)
    checks = workload.check(records)
    run = types.SimpleNamespace(
        counters=workload.counters(records[:n_traced]) if trace else counters,
        setup_s=setup_s, window_s=window_s, trace=tr, summary=summary, peaks=peak,
        config=r["config"], traffic=r["traffic"],
    )
    metrics = {}
    for m in r["per_layer"] if trace else r["end_to_end"]:
        v = r["readers"][m["name"]].read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": int(memory_peak),
    }
    result = {
        "correct": failed == 0 and all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": int(counters.get("runs", len(records))),
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if trace and summary is not None:
        device["busy_s"] = summary["busy_ns"] / 1e9
        device["window_s"] = summary["window_ns"] / 1e9
        result["breakdown"] = {
            "device_ops": [[n, v / 1e9] for n, v in summary["device_ops"]],
            "idle_gaps": [[n, v / 1e9] for n, v in summary["idle_gaps"]],
        }
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return result
