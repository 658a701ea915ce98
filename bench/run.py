"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic and metrics are found by name from
``BENCHMARK.json`` (see ``bench/harness.py``). With ``--trace 0`` the
result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window. The numbers
that decide ``correct`` are printed beside their limits as the last lines
of standard error and under ``checks`` in the result. The last line of
standard output is the result, a JSON object. Without a TPU, or without
the chips the cell asks for, the command prints why and exits 2.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        harness.resolve(args.workload)
        harness.use_compile_cache()
        result = harness.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace),
            t_start=T_START,
        )
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
