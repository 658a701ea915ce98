"""Benchmark orchestrator — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Select subsets with
``--only fig3,fig5``; the roofline table is produced separately from
dry-run records by ``python -m benchmarks.roofline``.

Named sweeps from `repro.experiments.registry` run directly:

  PYTHONPATH=src python -m benchmarks.run --sweep fig5 --out results/fig5.csv
  PYTHONPATH=src python -m benchmarks.run --sweep topology_grid --iters 400 --runs 2
  PYTHONPATH=src python -m benchmarks.run --sweep mesh_scale --mode sharded
  PYTHONPATH=src python -m benchmarks.run --list-sweeps

``--out FILE`` additionally persists the CSV rows (with header) to disk;
``--json FILE`` persists the machine-readable per-sweep engine summary
(wall-clock seconds + dispatch counts) that the benchmark-in-CI pipeline
regression-checks via ``python -m benchmarks.check`` (DESIGN.md §9).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from .common import Rows, peak_rss_mb

MODULES = ("fig3", "fig4", "fig5", "kernels")


def run_sweeps(names, rows: Rows, iters=None, runs=None, mode="auto") -> dict:
    """Run named sweeps; returns {sweep_name: engine summary} for --json."""
    import dataclasses

    from repro.experiments import Case, emit_rows, get_sweep, run_sweep

    kw = {}
    if iters is not None:
        kw["iters"] = iters
    if runs is not None:
        kw["runs"] = runs
    summaries = {}
    for name in names:
        spec = get_sweep(name, **kw)
        result = run_sweep(spec, mode=mode)
        # Reduce over the seed axis; group rows by every Case field that
        # actually varies across the grid (dict-valued axes may touch
        # several fields, so inspect the cases rather than the axis names).
        by = tuple(
            f.name for f in dataclasses.fields(Case)
            if f.name != "seed"
            and len({getattr(c, f.name) for c in result.cases}) > 1
        ) or ("method",)
        # Reduce on the sweep's declared evaluation axis (DESIGN.md §10):
        # the iteration index, or a cumulative field like "sim_time"
        # (accuracy at the shared time budget).
        emit_rows(result, rows, f"sweep/{spec.name}", by, x=spec.x_axis)
        summary = dict(
            wall_s=round(result.wall_s, 3),
            dispatches=result.n_dispatches,
            runs=len(result.cases),
            mode=result.mode,
            n_devices=result.n_devices,
            iters=result.cases[0].iters,
            # Process high-water RSS after this sweep: monotone across
            # sweeps, so the first sweep to raise it is the culprit of a
            # memory regression (gated by benchmarks.check).
            peak_rss_mb=round(peak_rss_mb(), 1),
        )
        summaries[spec.name] = summary
        rows.add(
            f"sweep/{spec.name}/engine", 0.0,
            ";".join(f"{k}={v}" for k, v in summary.items()),
        )
    return summaries


def write_json(path: str, summaries: dict) -> None:
    """BENCH_*.json: engine summaries + enough platform context to judge
    whether a wall-clock comparison is apples-to-apples."""
    import platform

    import jax

    payload = {
        "schema": 1,
        "python": platform.python_version(),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "n_devices": len(jax.devices()),
        "sweeps": summaries,
    }
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2, sort_keys=True)
        fh.write("\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--only", default=None,
        help=f"comma-separated subset of {MODULES}",
    )
    ap.add_argument(
        "--sweep", default=None,
        help="comma-separated named sweeps from repro.experiments.registry "
        "(skips the figure modules)",
    )
    ap.add_argument("--list-sweeps", action="store_true")
    ap.add_argument("--iters", type=int, default=None,
                    help="override sweep iteration count (smoke runs)")
    ap.add_argument("--runs", type=int, default=None,
                    help="override sweep seed count")
    ap.add_argument("--serial", action="store_true",
                    help="run sweeps through the per-run serial path "
                    "(reference/timing baseline)")
    ap.add_argument("--mode", default=None,
                    choices=("auto", "serial", "batched", "sharded"),
                    help="sweep execution tier (DESIGN.md §9); default "
                    "auto = sharded iff >1 device is visible")
    ap.add_argument("--out", default=None, metavar="FILE",
                    help="also write the CSV rows (with header) to FILE")
    ap.add_argument("--json", default=None, metavar="FILE",
                    help="write the per-sweep engine summary (wall_s + "
                    "dispatch counts) as JSON for benchmarks.check")
    args = ap.parse_args(argv)
    if args.serial and args.mode not in (None, "serial"):
        ap.error("--serial contradicts --mode " + args.mode)
    if args.json and not args.sweep:
        ap.error("--json requires --sweep (engine summaries)")
    mode = "serial" if args.serial else args.mode or "auto"

    if args.list_sweeps:
        from repro.experiments import SWEEPS, get_sweep

        for name in sorted(SWEEPS):
            print(f"{name}: {get_sweep(name).description}")
        return 0

    rows = Rows()
    t0 = time.time()
    summaries = {}
    if args.sweep:
        summaries = run_sweeps(
            args.sweep.split(","), rows,
            iters=args.iters, runs=args.runs, mode=mode,
        )
    else:
        selected = args.only.split(",") if args.only else list(MODULES)
        if "fig3" in selected:
            from . import fig3_usps

            fig3_usps.run(rows)
        if "fig4" in selected:
            from . import fig4_ijcnn1

            fig4_ijcnn1.run(rows)
        if "fig5" in selected:
            from . import fig5_stragglers

            fig5_stragglers.run(rows)
        if "kernels" in selected:
            from . import kernels_micro

            kernels_micro.run(rows)

    print(Rows.HEADER)
    rows.emit()
    if args.out:
        rows.write_csv(args.out)
        print(f"# wrote {len(rows.rows)} rows to {args.out}", file=sys.stderr)
    if args.json:
        write_json(args.json, summaries)
        print(
            f"# wrote {len(summaries)} sweep summaries to {args.json}",
            file=sys.stderr,
        )
    print(f"# total {time.time() - t0:.1f}s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
