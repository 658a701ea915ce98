"""Training launcher.

Runs REAL steps (CPU-sized configs train here; full configs are exercised
via the dry-run). Two modes:

  plain      — standard Adam training (PlainRuntime)
  consensus  — the paper's csI-ADMM across simulated agents
               (ConsensusRuntime; straggler events sampled per step)

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
      --steps 50 --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
      --mode consensus --agents 2 --ecns 4 --stragglers 1 --steps 100
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_step
from repro.compile_cache import enable_compilation_cache
from repro.configs import get_config, get_smoke_config
from repro.data import agent_token_streams, make_lm_batch
from repro.distributed import ConsensusConfig, ConsensusRuntime, PlainRuntime
from repro.models import get_model
from repro.optim import adam_init


def _mesh_1dev():
    return jax.make_mesh((1, 1, 1), ("agent", "data", "model"))


def run_plain(model, args) -> dict:
    rt = PlainRuntime(model, _mesh_1dev(), lr=args.lr)
    params = model.init(jax.random.key(args.seed))
    state = {"params": params, "opt": adam_init(params)}
    step = jax.jit(rt.train_step)
    stream = agent_token_streams(1, model.cfg.vocab, seed=args.seed)[0]
    losses = []
    t0 = time.time()
    for k in range(args.steps):
        batch = jax.tree.map(
            jnp.asarray, make_lm_batch(stream, args.batch, args.seq)
        )
        if model.cfg.modality == "vision_stub":
            batch["extra_embeds"] = jnp.ones(
                (args.batch, 16, model.cfg.d_model), model.cfg.jnp_dtype
            ) * 0.01
        elif model.cfg.modality == "audio_stub":
            batch["extra_embeds"] = jnp.ones(
                (args.batch, model.cfg.encoder_positions, model.cfg.d_model),
                model.cfg.jnp_dtype,
            ) * 0.01
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
        if k % args.log_every == 0 or k == args.steps - 1:
            print(
                f"step {k:5d}  loss {losses[-1]:.4f}  "
                f"({(time.time() - t0) / (k + 1):.2f}s/step)",
                flush=True,
            )
        if args.ckpt_dir and (k + 1) % args.ckpt_every == 0:
            save_step(args.ckpt_dir, k + 1, state["params"])
    return {"losses": losses, "state": state}


def run_consensus(model, args) -> dict:
    ccfg = ConsensusConfig(
        n_agents=args.agents,
        K=args.ecns,
        S=args.stragglers,
        scheme=args.scheme if args.stragglers else "uncoded",
        rho=args.rho,
        c_tau=args.c_tau,
        c_gamma=args.c_gamma,
        mode=args.consensus_mode,
        seed=args.seed,
    )
    rt = ConsensusRuntime(model, ccfg, _mesh_1dev())
    state = rt.init_state(jax.random.key(args.seed))
    # The state is donated: x, y and z are rewritten every step, and at
    # full width two live copies of them do not fit one chip.
    step = jax.jit(rt.train_step, donate_argnums=0)
    code = ccfg.code()
    sup = [code.support(j) for j in range(args.ecns)]
    # disjoint stream per agent (paper's allocation)
    streams = agent_token_streams(args.agents, model.cfg.vocab, seed=args.seed)
    rng = np.random.default_rng(args.seed + 7)
    A, K, S1 = args.agents, args.ecns, args.stragglers + 1
    P_rows = max(args.batch // (A * K * S1), 1)
    losses, residuals = [], []
    t0 = time.time()
    for k in range(args.steps):
        # coded allocation: sample each agent's K distinct partitions, then
        # lay out partition t on every ECN whose support contains it.
        rows = []
        for a in range(A):
            parts = [
                make_lm_batch(streams[a], P_rows, args.seq) for _ in range(K)
            ]
            for j in range(K):
                for t in sup[j]:
                    rows.append(parts[t])
        batch = {
            key: jnp.concatenate([r[key] for r in rows], axis=0)
            for key in rows[0]
        }
        alive = np.ones((A, K), bool)
        for a in range(A):  # straggler event: drop up to S random ECNs
            dead = rng.choice(K, size=args.stragglers, replace=False)
            alive[a, dead] = False
        state, metrics = step(state, batch, jnp.asarray(alive))
        losses.append(float(metrics["loss"]))
        residuals.append(float(metrics["consensus_residual"]))
        if k % args.log_every == 0 or k == args.steps - 1:
            print(
                f"step {k:5d}  loss {losses[-1]:.4f}  "
                f"residual {residuals[-1]:.3e}  "
                f"({(time.time() - t0) / (k + 1):.2f}s/step)",
                flush=True,
            )
        if args.ckpt_dir and (k + 1) % args.ckpt_every == 0:
            save_step(args.ckpt_dir, k + 1, state["z"])
    return {"losses": losses, "residuals": residuals, "state": state}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--mode", choices=("plain", "consensus"), default="plain")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument(
        "--remat", choices=("none", "full", "dots"), default=None,
        help="activation checkpointing of the layer scan (default: config's)",
    )
    # consensus
    ap.add_argument("--agents", type=int, default=2)
    ap.add_argument("--ecns", type=int, default=4)
    ap.add_argument("--stragglers", type=int, default=1)
    # NN-scale defaults: the x-update's effective step is 1/(rho + tau^k),
    # so c_tau ~ 20 gives ~0.05 at k=1 decaying as 1/sqrt(k) (the paper's
    # least-squares settings rho=1, c_tau~0.1 diverge on NN losses).
    ap.add_argument("--scheme", default="cyclic")
    ap.add_argument("--rho", type=float, default=1.0)
    ap.add_argument("--c-tau", type=float, default=20.0)
    ap.add_argument("--c-gamma", type=float, default=0.1)
    ap.add_argument(
        "--consensus-mode", choices=("incremental", "parallel"), default="incremental"
    )
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.remat is not None:
        cfg = dataclasses.replace(cfg, remat=args.remat)
    model = get_model(cfg)
    enable_compilation_cache()
    print(
        f"training {args.arch} ({'smoke' if args.smoke else 'full'}) "
        f"mode={args.mode} params={cfg.param_count():,}"
    )
    if args.mode == "plain":
        out = run_plain(model, args)
    else:
        out = run_consensus(model, args)
    first, last = out["losses"][0], out["losses"][-1]
    print(f"loss: {first:.4f} -> {last:.4f}")
    return out


if __name__ == "__main__":
    main()
