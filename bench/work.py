"""Operations and bytes that the measured work needs, from its shapes.

These count what the algorithm has to do, whatever implements it: a
padded tile, a recomputation or a second copy does not count. Bytes are
at the configuration's item size (float32: 4).
"""

from __future__ import annotations

from typing import Tuple


def coded_admm_update(J: int, n: int, itemsize: int = 4) -> Tuple[int, int]:
    """Fused decode-combine + x-update (eq. 6 then 5a) on n floats from J
    messages: x+ = (tau x + rho z + y - sum_j c_j m_j) / (rho + tau).

    Reads the (J, n) messages and x, y, z once, writes x+ once. Per
    element: J multiply-adds for the combine, then 2 multiplies, 3 adds
    and a divide for the update.
    """
    flops = 2 * J * n + 6 * n
    nbytes = (J + 3) * n * itemsize + n * itemsize
    return flops, nbytes


def admm_run_iteration(
    N: int, K: int, mu: int, p: int, d: int, fields: int, summaries: int,
    itemsize: int = 4,
) -> Tuple[int, int]:
    """One iteration of one csI-ADMM least-squares run, with its metrics
    and the streaming fold of ``fields`` metrics into ``summaries`` state
    slots each (budgets + targets + running statistics).

    - the K partitions' mini-batch gradients: mu rows each, residual
      O x - t and O^T r (4 p d + d per row), scaled by 1/mu;
    - decode-combine and eqs. 5a, 5b, 4c on n = p d floats;
    - accuracy (eq. 23) over N agents, and the test error of z through
      the test set's p x p Gram matrix (2 p^2 d + 4 n);
    - the fold: a few operations per state slot.

    Bytes: the K mu gathered rows of O and T, x_i, y_i and z read and
    written, x read for the accuracy, and the step's schedule inputs
    (2K decode weights and mask, agent, offset, tau, gamma, 2 clocks).
    """
    n = p * d
    grad = K * mu * (4 * n + d) + K * n
    combine, kbytes = coded_admm_update(K, n, itemsize)
    update = 4 * n + 5 * n
    metrics = N * (3 * n + 2) + 2 * p * p * d + 4 * n
    fold = fields * summaries * 4
    flops = grad + combine + update + metrics + fold
    nbytes = (
        K * mu * (p + d) * itemsize
        + 6 * n * itemsize
        + N * n * itemsize
        + (2 * K + 6) * itemsize
    )
    return flops, nbytes


def train_flops_per_token(
    layers: int, d_model: int, heads: int, kv_heads: int, head_dim: int,
    d_ff: int, vocab: int, seq: int,
) -> int:
    """Forward and backward of a dense decoder per trained token: 6 times
    the matrix parameters touched (attention q/k/v/o, gated MLP, the
    vocabulary projection) plus causal attention's scores and values,
    6 * 2 * layers * heads * head_dim * seq / 2. Recomputation from
    activation checkpointing is not counted.
    """
    attn = d_model * head_dim * (2 * heads + 2 * kv_heads)
    mlp = 3 * d_model * d_ff
    params = layers * (attn + mlp) + d_model * vocab
    return 6 * params + 6 * layers * heads * head_dim * seq
