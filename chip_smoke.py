"""Bring-up run on a TPU: the csI-ADMM sweep engine and the consensus trainer.

Everything runs in this one process, through the entry points a user
calls (a process that has touched JAX holds the chip, so no phase may run
in a child):

1. device: JAX's first device must be a TPU;
2. sweep engine at the registered sizes: ``fig5`` (16 runs x 1200
   iterations, full Traces) and ``fleet_frontier`` at 100 seeds (1,200
   cases x 1000 iterations, streaming reductions); the fused
   ``coded_admm_update`` step kernel must compile natively
   (``tpu_custom_call``), not in interpret mode;
3. correctness: each fig5 run's final accuracy (eq. 23) recomputed on the
   host in float64 numpy from the run's final iterates and the closed-form
   optimum, against the device's own float32 value; every fleet_frontier
   summary finite;
4. trainer: three csI-ADMM consensus steps of Qwen3-0.6B at its published
   widths in bf16; every loss and consensus residual finite.

With ``--chips 4`` only the four-chip phase runs: fig5 sharded over four
chips against the same grid batched on one, Traces bitwise equal.

The last line of stdout is ``{"ok": true, "device": {...}}``. A failed
phase exits non-zero without printing it.

    python chip_smoke.py
    python chip_smoke.py --chips 4
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

# Final fig5 accuracy, device float32 vs host float64. The metric is a
# mean of ||x_i - x*|| / ||x*|| over agents; rounding x* and the norms to
# float32 moves it by a few float32 ulps of 1 (~1e-7), so 1e-6 plus 1e-5
# of the value leaves a decade of room and still catches a wrong iterate.
ACC_ATOL = 1e-6
ACC_RTOL = 1e-5

TRAIN_ARGV = [
    "--arch", "qwen3-0.6b", "--mode", "consensus",
    "--agents", "2", "--ecns", "4", "--stragglers", "1",
    "--batch", "16", "--seq", "128", "--steps", "3", "--log-every", "1",
    # Compiled for one v5e, the full-width step with the donated state
    # needs 16.17 GB of its 15.75 GB of HBM without activation
    # checkpointing of the layer scan, and 14.0 GB with it.
    "--remat", "full",
]

TRACE_FIELDS = ("accuracy", "test_error", "z_err", "final_x", "final_z")


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes() -> int:
    """Largest peak of bytes in use over the devices, for this process."""
    import jax

    return max(d.memory_stats()["peak_bytes_in_use"] for d in jax.devices())


def device_phase(chips: int) -> dict:
    import jax

    devices = jax.devices()
    d = devices[0]
    log(
        f"jax {jax.__version__}; device platform {d.platform}, "
        f"kind {d.device_kind!r}, count {len(devices)}"
    )
    check(d.platform == "tpu", f"JAX found no TPU (platform {d.platform})")
    check(
        len(devices) == chips,
        f"--chips {chips} needs {chips} devices, found {len(devices)}",
    )
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def timed_sweep(name: str, mode: str = "auto", **overrides):
    from repro.experiments import get_sweep, run_sweep

    spec = get_sweep(name, **overrides)
    t0 = time.perf_counter()
    res = run_sweep(spec, mode=mode)
    seconds = time.perf_counter() - t0
    log(
        f"{name}: {len(res.cases)} cases x {res.cases[0].iters} iterations, "
        f"mode {res.mode}, {res.n_dispatches} dispatch(es), "
        f"{seconds:.3f} s wall (compile included), "
        f"process peak device bytes {peak_bytes()}"
    )
    return res


def check_kernel_native(fig5) -> None:
    """The step's fused kernel, lowered at fig5's shapes, is Mosaic code."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    J = fig5.cases[0].K
    n = int(np.prod(fig5.traces[0].final_z.shape))

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    text = ops.coded_admm_update.lower(
        f32(J, n), f32(J), f32(n), f32(n), f32(n), f32(), f32(), f32(J),
        block_n=ops.fit_block_n(n),
    ).compile().as_text()
    check(
        "tpu_custom_call" in text,
        "coded_admm_update compiled without tpu_custom_call (interpreted?)",
    )
    log(f"coded_admm_update (J={J}, n={n}): tpu_custom_call present")


def check_fig5(fig5) -> None:
    from repro.core.problems import DATASETS, allocate

    worst = 0.0
    for case, tr in zip(fig5.cases, fig5.traces):
        prob = allocate(DATASETS[case.dataset](case.seed), case.N, case.K)
        x = np.asarray(tr.final_x, np.float64)
        ref = prob.accuracy(x, prob.x_star(), np.zeros_like(x))
        dev = float(tr.accuracy[-1])
        err = abs(dev - ref)
        check(
            err <= ACC_ATOL + ACC_RTOL * abs(ref),
            f"fig5 {case.label('S', 'seed')}: device accuracy {dev!r} vs "
            f"host float64 {ref!r}",
        )
        check(
            dev < float(tr.accuracy[0]),
            f"fig5 {case.label('S', 'seed')}: accuracy did not improve",
        )
        worst = max(worst, err)
    log(
        f"fig5 check: {len(fig5.cases)} runs, max |device - host f64| final "
        f"accuracy {worst:.3e} (limit {ACC_ATOL} + {ACC_RTOL} x value)"
    )


def check_fleet(fleet) -> None:
    for key, val in fleet.reduced.items():
        check(bool(np.all(np.isfinite(val))), f"fleet_frontier {key}: non-finite")
    log(f"fleet_frontier check: {len(fleet.reduced)} summaries, all finite")


def trainer_phase() -> None:
    from repro.launch import train

    t0 = time.perf_counter()
    out = train.main(TRAIN_ARGV)
    seconds = time.perf_counter() - t0
    vals = np.asarray(out["losses"] + out["residuals"], np.float64)
    check(len(out["losses"]) == 3, "trainer did not take 3 steps")
    check(bool(np.all(np.isfinite(vals))), f"trainer non-finite: {vals}")
    log(
        f"trainer: 3 consensus steps, {seconds:.3f} s wall (init and compile "
        f"included), process peak device bytes {peak_bytes()}"
    )


def sharded_phase() -> None:
    """fig5 over four chips (``mode="sharded"``) against one (batched)."""
    import jax

    from repro.methods import driver

    spans = []
    build = driver._batch_fn

    def spy(kernel, statics_key, shared, D, donate):
        fn = build(kernel, statics_key, shared, D, donate)
        if D == 1:  # a one-run group's structural fallback
            return fn

        def call(*args):
            spans.append(
                {
                    d.id
                    for a in jax.tree.leaves(args)
                    for d in a.sharding.device_set
                }
            )
            return fn(*args)

        return call

    driver._batch_fn = spy
    try:
        sharded = timed_sweep("fig5", mode="sharded")
    finally:
        driver._batch_fn = build
    check(bool(spans), "the sharded sweep never reached the sharded path")
    check(
        all(len(s) == 4 for s in spans),
        f"sharded inputs spanned devices {spans}, want 4",
    )
    batched = timed_sweep("fig5", mode="batched")
    check(sharded.cases == batched.cases, "sharded and batched grids differ")
    worst = 0.0
    for ts, tb in zip(sharded.traces, batched.traces):
        for f in TRACE_FIELDS:
            a, b = np.asarray(getattr(ts, f)), np.asarray(getattr(tb, f))
            worst = max(worst, float(np.max(np.abs(a - b))))
    log(
        f"fig5 sharded vs batched: {len(spans)} dispatch(es) over devices "
        f"{sorted(spans[0])}; max |difference| over {TRACE_FIELDS} = {worst!r}"
    )
    check(worst == 0.0, "sharded and batched fig5 Traces are not bitwise equal")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only fig5 sharded over four chips vs batched",
    )
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        device = device_phase(args.chips)
        from repro.compile_cache import enable_compilation_cache

        enable_compilation_cache()
        if args.chips == 4:
            sharded_phase()
        else:
            fig5 = timed_sweep("fig5")
            check_kernel_native(fig5)
            check_fig5(fig5)
            check_fleet(timed_sweep("fleet_frontier", runs=100))
            trainer_phase()
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
