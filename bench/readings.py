"""Readings that the limits of ``correct`` are set from, for one cell.

    python bench/readings.py --workload <cell> --seeds 1-12 --control-seeds 1-3 \
        [--faults half_batch]

For each program seed: the cell's set-up and one step of its traffic (for
a sweep, one whole job) through the timed path on the chip, then the
cell's check. For each control seed: the cell's check with the plain
reference computed in the next lower precision put in the program's
place, and with each named fault planted in the reference put there (a
generator that offers ``fault``). One process. Prints a JSON line per
seed, then the largest reading of the program (the lower reading) and the
smallest of the control and of each fault (the upper readings) of each
number, beside the limit the traffic file holds.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402

LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def seed_list(text: str) -> list:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seed_list, default=[])
    ap.add_argument("--control-seeds", type=seed_list, default=[])
    ap.add_argument("--faults", nargs="*", default=[])
    args = ap.parse_args(argv)
    r = harness.resolve(args.workload)
    harness.use_compile_cache()
    try:
        harness.devices(r["cell"]["chips"])
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(harness.ROOT / "src"))
    limits = r["traffic"]["check"]["limits"]
    lower = {k: 0.0 for k in limits}
    dtype = LOWER[r["config"]["dtype"]]
    sides = [f"control {dtype}"] + [f"fault {f}" for f in args.faults]
    upper = {side: {k: float("inf") for k in limits} for side in sides}
    for seed in args.seeds:
        workload = r["generator"].Workload(r["config"], r["traffic"], seed)
        workload.warm_up()
        t0 = time.perf_counter()
        records = [workload.step(0)]
        t1 = time.perf_counter()
        checks = workload.check(records)
        t2 = time.perf_counter()
        for k, c in checks.items():
            lower[k] = max(lower[k], c["value"])
        print(json.dumps({"seed": seed, "side": "program", "step_s": t1 - t0,
                          "check_s": t2 - t1, "checks": checks}), flush=True)
    for seed in args.control_seeds:
        for side in sides:
            workload = r["generator"].Workload(r["config"], r["traffic"], seed)
            t0 = time.perf_counter()
            kind, name = side.split(" ", 1)
            checks = workload.control(name) if kind == "control" else workload.fault(name)
            for k, c in checks.items():
                upper[side][k] = min(upper[side][k], c["value"])
            print(json.dumps({"seed": seed, "side": side,
                              "check_s": time.perf_counter() - t0, "checks": checks}), flush=True)
    print(json.dumps({"lower": lower, "upper": upper, "limits": limits}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
