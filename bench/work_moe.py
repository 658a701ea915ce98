"""Operations and bytes of the MLA and expert-layer training work of the
``moonlight-16b-a3b`` configuration, from its shapes and the token-slots
actually routed (``bench/work.py``'s conventions: what the algorithm has
to do; padding, recomputation and second copies do not count unless
said). Bytes at the configuration's item size (bfloat16: 2)."""

from __future__ import annotations

from typing import Tuple


def mla_params(m: dict) -> int:
    """Matrix parameters of one MLA block: W_q, W_kv_a, W_kv_b, W_o."""
    D, H, r = m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return D * H * (dn + dr) + D * (r + dr) + r * H * (dn + dv) + H * dv * D


def train_flops_per_token(m: dict, seq: int) -> int:
    """Forward and backward per trained token of everything but the
    routed experts: 6 times the matrix parameters every token touches
    (MLA, the dense layer's SwiGLU, the shared experts, the router, the
    head over the vocabulary held) plus causal attention, scores over
    qk_nope + qk_rope dims and values of v_head_dim, 3 * 2 * heads * (dn
    + dr + dv) * seq / 2 a layer. Recomputation is not counted."""
    D = m["hidden_size"]
    Ld = m["first_k_dense_replace"]
    Le = m["layers_held"] - Ld
    shared = 3 * D * m["n_shared_experts"] * m["moe_intermediate_size"]
    params = (
        m["layers_held"] * mla_params(m)
        + Ld * 3 * D * m["intermediate_size"]
        + Le * (shared + D * m["n_routed_experts"])
        + D * m["vocab_held"]
    )
    head_width = m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"]
    attn = 3 * m["layers_held"] * m["num_attention_heads"] * head_width * seq
    return 6 * params + attn


def routed_flops(m: dict, rows: int) -> int:
    """Forward and backward of the held experts for ``rows`` routed
    token-slots (summed over layers): 6 times a SwiGLU expert's 3 D F
    parameters per slot."""
    return 6 * 3 * m["hidden_size"] * m["moe_intermediate_size"] * rows


def expert_gmm_step(m: dict, rows: int, passes: int, forward_runs: int,
                    itemsize: int = 2) -> Tuple[int, int]:
    """Least operations and bytes of the grouped expert products that a
    training step runs: ``rows`` token-slots routed to the held experts
    (summed over the ``passes`` expert-layer passes, one per layer and
    agent), each pass running its three forward products (gate, up: D ->
    F; down: F -> D) ``forward_runs`` times (2 where the layer's forward
    is recomputed for the backward pass) and, once, their three data
    gradients (the same products, weights transposed) and three weight
    gradients.

    Each product moves its input rows and output rows once and its held
    experts' weights once: a row costs D + F items a product, a pass
    3 E_held D F items a product."""
    D, F, Eh = m["hidden_size"], m["moe_intermediate_size"], m["experts_held"]
    products = 3 * forward_runs + 6
    flops = 2 * D * F * rows * products
    nbytes = itemsize * ((D + F) * rows * products + Eh * D * F * passes * products)
    return flops, nbytes
