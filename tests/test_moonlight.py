"""Moonlight-16B-A3B's blocks (MLA, DeepSeek-V3 expert layers) against the
plain reference `bench/reference/moonlight.py`, on seeded random weights
at a small size on the CPU: width 64, a latent of 32, rope 16, nope 32 and
v 32 per head, 16 experts with 4 held, top 4, one shared expert, one
dense and two expert layers.

Program and reference both compute in float32 here; they differ by the
order of float32 operations (the program rotates, concatenates and
accumulates in another order), so values agree to about 1e-6 of their
scale, and the tolerances below leave a few times that. Routing choices
are the same in both (no score lies within round-off of a tie at these
seeds)."""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.distributed import ConsensusConfig, ConsensusRuntime
from repro.models import get_model
from repro.models import transformer
from repro.models.layers import moe_apply, moe_share_apply

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))

from reference import moonlight as ref  # noqa: E402

CFG = get_smoke_config("moonlight-16b-a3b")
SEED = 2**31 + 77
B, S = 2, 24


def dims(cfg=CFG, experts_held=None, expert_offset=0):
    """The reference's numbers for a program configuration."""
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "kv_lora_rank": cfg.kv_lora_rank, "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim, "v_head_dim": cfg.v_head_dim,
        "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "intermediate_size": cfg.d_ff, "first_k_dense_replace": cfg.first_dense_layers,
        "n_routed_experts": cfg.n_experts, "num_experts_per_tok": cfg.experts_per_token,
        "moe_intermediate_size": cfg.d_expert, "n_shared_experts": cfg.n_shared_experts,
        "routed_scaling_factor": cfg.routed_scale,
        "layers_held": cfg.n_layers, "experts_held": experts_held or cfg.n_experts_held,
        "expert_offset": expert_offset, "vocab_held": cfg.vocab, "router_bias_seed": 0,
    }


@pytest.fixture(scope="module")
def params():
    return ref.init(dims(), SEED, "float32")


def _tokens(seed=0, rows=B):
    rng = np.random.default_rng(seed)
    raw = rng.integers(0, CFG.vocab, size=(rows, S + 1), dtype=np.int32)
    return jnp.asarray(raw[:, :-1]), jnp.asarray(raw[:, 1:])


def _hidden(seed=3):
    return jax.random.normal(jax.random.key(seed), (B, S, CFG.d_model), jnp.float32)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def _layer(params, stack="layers", i=0):
    return jax.tree.map(lambda a: a[i], params[stack])


def test_parameter_counts_of_the_published_model_and_the_cut():
    from repro.configs import get_config

    full = get_config("moonlight-16b-a3b")
    assert round(full.param_count() / 1e9, 2) == 15.96
    # One chip's share: 5 layers, 8 of 64 experts, an eighth of the
    # vocabulary (the benchmark's cut): 568.5M parameters.
    cut = dataclasses.replace(full, n_layers=5, experts_held=8, vocab=20480)
    assert cut.param_count() == 568_484_608
    assert cut.active_param_count() < cut.param_count()


def test_program_init_has_the_reference_layout(params):
    got = jax.eval_shape(lambda: get_model(CFG).init(jax.random.key(0)))
    shapes = lambda t: jax.tree.map(lambda a: (a.shape, str(a.dtype)), t)  # noqa: E731
    assert shapes(got) == shapes(params)


@pytest.mark.parametrize("stack", ["dense_layers", "layers"])
def test_mla_block_matches_reference(params, stack):
    lp = _layer(params, stack)
    x = _hidden()
    got, (c_kv, k_rope) = transformer._mla_attention(
        CFG, lp, x, transformer._positions(CFG, B, S))
    want = jax.vmap(lambda row: ref._attention(dims(), row, lp))(x)
    assert _rel(got, want) < 1e-5
    assert c_kv.shape == (B, S, CFG.kv_lora_rank)
    assert k_rope.shape == (B, S, 1, CFG.qk_rope_head_dim)


def _share(lp, cfg=CFG, impl="jnp", **kw):
    x = _hidden().reshape(B * S, cfg.d_model)
    return moe_share_apply(
        x, lp, cfg.n_experts, cfg.experts_per_token, kw.get("offset", cfg.expert_offset),
        cfg.routed_scale, impl=impl,
    )


def _reference_layer(lp, m, shared=True):
    """The reference's expert layer on the same inputs, with or without
    the shared experts."""
    a = _hidden().reshape(B * S, CFG.d_model)
    w = ref._routing(m, a, lp, None)
    out = ref._experts(m, a, w, lp)
    if not shared:
        out = out - ref._swiglu(a, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    return out


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_expert_layer_matches_reference(params, impl):
    lp = _layer(params, "layers", 1)
    got, counters = _share(lp, impl=impl)
    assert _rel(got, _reference_layer(lp, dims())) < 1e-5
    held = int(counters["held_rows"])
    assert 0 < held < B * S * CFG.experts_per_token
    assert 0 < int(counters["max_rows"]) <= held
    assert int(counters["dropped"]) == 0


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips of 4 experts each: the routed parts of the four shares,
    with the shared expert counted once, are the uncut layer."""
    full = dataclasses.replace(CFG, experts_held=CFG.n_experts)
    lp = _layer(ref.init(dims(full), SEED, "float32"), "layers", 0)
    shared = ref._swiglu(_hidden().reshape(B * S, CFG.d_model), lp["shared_gate"],
                         lp["shared_up"], lp["shared_down"])
    total = np.asarray(shared)
    Eh = CFG.n_experts_held
    for off in range(0, CFG.n_experts, Eh):
        part = dict(lp, **{k: lp[k][off:off + Eh] for k in ("w_gate", "w_up", "w_down")})
        out, _ = _share(part, offset=off)
        total = total + (np.asarray(out) - np.asarray(shared))
    assert _rel(total, _reference_layer(lp, dims(full))) < 1e-5


def test_no_token_is_dropped_under_any_imbalance(params):
    """Every token routed to the same four held experts: the dropless
    share equals the reference; the capacity path of `moe_apply` (the
    same experts, chosen the same way, capacity factor 1.25) drops slots
    and fails the same comparison."""
    cfg = dataclasses.replace(CFG, routed_scale=1.0)
    k = cfg.experts_per_token
    lp = dict(_layer(params, "layers", 0))
    lp["router"] = jnp.zeros_like(lp["router"])  # all scores 1/2 (softmax: 1/E)
    lp["router_bias"] = jnp.zeros_like(lp["router_bias"]).at[:k].set(1.0)
    want = _reference_layer(lp, dims(cfg), shared=False)

    out, counters = _share(lp, cfg)
    shared = ref._swiglu(_hidden().reshape(B * S, cfg.d_model), lp["shared_gate"],
                         lp["shared_up"], lp["shared_down"])
    assert int(counters["held_rows"]) == B * S * k
    assert int(counters["max_rows"]) == B * S
    assert _rel(np.asarray(out) - np.asarray(shared), want) < 1e-5

    # moe_apply holds every expert: the held four first, the rest unused.
    E = cfg.n_experts
    full = {
        w: jnp.concatenate([lp[w], jnp.zeros((E - cfg.n_experts_held, *lp[w].shape[1:]))])
        for w in ("w_gate", "w_up", "w_down")
    }
    assert (jax.lax.top_k(jnp.zeros((1, E)), k)[1] == jnp.arange(k)).all()
    capped, _ = moe_apply(_hidden().reshape(B * S, cfg.d_model), dict(full, router=lp["router"]),
                          E, k, capacity_factor=1.25)
    assert _rel(capped, want) > 0.1


def test_loss_and_gradients_match_reference(params):
    tokens, labels = _tokens()
    w = jnp.asarray([0.3, 0.7], jnp.float32)
    model = get_model(CFG)
    batch = {"tokens": tokens, "labels": labels, "loss_weights": w}
    (loss, metrics), grads = jax.value_and_grad(model.loss, has_aux=True)(params, batch)
    want, want_grads = jax.value_and_grad(
        lambda p: ref.loss(dims(), p, tokens, labels, w))(params)
    assert abs(float(loss) - float(want)) < 1e-6 * abs(float(want))
    assert int(metrics["moe/dropped"]) == 0 and int(metrics["moe/held_rows"]) > 0
    got, exp = ref.flatten(grads), ref.flatten(want_grads)
    assert not np.asarray(got["layers/router_bias"]).any()
    for name in exp:
        if name == "layers/router_bias":
            continue
        scale = max(float(jnp.abs(exp[name]).max()), 1e-12)
        err = float(jnp.abs(got[name] - exp[name]).max()) / scale
        assert err < 1e-4, (name, err)


def test_two_consensus_steps_match_reference():
    """Two csI-ADMM steps of the trainer against the reference's, from
    the same weights, batches and stragglers, state kept in float32."""
    cons = {"n_agents": 2, "K": 4, "S": 1, "scheme": "cyclic", "code_seed": 0,
            "rho": 1.0, "c_tau": 20.0, "c_gamma": 0.1, "mode": "incremental"}
    rt = ConsensusRuntime(
        get_model(CFG),
        ConsensusConfig(n_agents=2, K=4, S=1, scheme="cyclic", rho=1.0, c_tau=20.0,
                        c_gamma=0.1),
        jax.make_mesh((1, 1, 1), ("agent", "data", "model")),
    )
    m = dims()
    p0 = ref.init(m, SEED, "float32")
    state = {
        "x": jax.tree.map(lambda a: jnp.stack([a, a]), p0),
        "y": jax.tree.map(lambda a: jnp.zeros((2, *a.shape), a.dtype), p0),
        "z": p0, "k": jnp.zeros((), jnp.int32),
    }
    sup = np.asarray(rt.support)  # (ECN, stored partition)
    rng = np.random.default_rng(5)
    batches, alive, losses = [], [], []
    step = jax.jit(rt.train_step)
    for k in range(2):
        tok, lab = _tokens(seed=10 + k, rows=8)  # 2 agents x 4 partitions
        rows = [(a * 4 + t) for a in range(2) for j in range(4) for t in sup[j]]
        batch = {"tokens": np.asarray(tok)[rows], "labels": np.asarray(lab)[rows]}
        live = np.ones((2, 4), bool)
        live[np.arange(2), rng.integers(0, 4, size=2)] = False
        state, metrics = step(state, {k_: jnp.asarray(v) for k_, v in batch.items()},
                              jnp.asarray(live))
        if k == 0:
            grad = ref.leaf_norms(state["x"], p0, 1.0 + 20.0, index=0)
        assert int(metrics["moe/dropped"]) == 0
        assert 0 < int(metrics["moe/committed_rows"]) < int(metrics["moe/held_rows"])
        batches.append(batch)
        alive.append(live)
        losses.append(float(metrics["loss"]))
    want = ref.run(m, cons, SEED, batches, alive, store="float32", weights_dtype="float32")
    np.testing.assert_allclose(losses, want["loss"], rtol=1e-5)
    got = {"grad_norm": grad, "z_change": ref.leaf_norms(state["z"], p0)}
    for number in got:
        floor = float(np.median(list(want[number].values())))
        for name, v in want[number].items():
            assert abs(got[number][name] - v) <= 1e-4 * max(v, floor), (number, name)
