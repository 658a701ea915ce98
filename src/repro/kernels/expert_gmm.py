"""Grouped matrix products over the experts a chip holds (Pallas TPU).

The rows of ``lhs`` come sorted by expert: group g owns rows
[offsets[g], offsets[g + 1]), where offsets is the running sum of
``group_sizes``. Rows past the last group carry no token-slot. The group
sizes come in by scalar prefetch, and the grid's row axis runs over the
row tiles the groups touch and no further, so the work follows the rows
actually routed and not the buffer they sit in: a worst-case buffer whose
tail is empty costs nothing past its last group. Rows of the result past
the last group read 0.

- ``gmm``: out[r] = lhs[r] @ rhs[g(r)], rhs (G, k, n), or rhs[g(r)]^T with
  ``transpose_rhs`` (rhs (G, n, k)), for the data gradient;
- ``tgmm``: out[g] = lhs[rows of g]^T @ dout[rows of g], the weight
  gradient (G, k, n); an empty group's gradient is 0.

``grouped_matmul`` ties them into one differentiable op (custom VJP: the
data gradient is ``gmm`` with the weights transposed, the weight gradient
``tgmm``). Under ``vmap`` (the consensus trainer's agent axis) each batch
entry runs as its own kernel call, one after another, since a grid whose
extent is read from the data cannot be batched. Every ``pallas_call`` is
named ``expert_gmm*`` so that a device trace shows it by name.

Adapted from the megablox kernels that ship with JAX
(``jax.experimental.pallas.ops.tpu.megablox``): the same group metadata
and tile masks, without its sharded group offset and existing-output
paths.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gmm", "tgmm", "grouped_matmul", "pick_tiles"]

LANE = 128


def _tile(dim: int, target: int, full_up_to: int) -> int:
    """The whole dimension if it is small, else the largest multiple of
    the lane width that divides it and is at most ``target``."""
    if dim <= full_up_to or dim % LANE:
        return dim
    t = max(LANE, target // LANE * LANE)
    while dim % t:
        t -= LANE
    return t


def pick_tiles(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """(tm, tk, tn): 512-row tiles (fewer rows for a short buffer, padded
    to 16, the bfloat16 sublane tile); k and n whole up to 1,536 (the
    expert width 1,408 has no 128-multiple divisor between 128 and
    itself), else in 512-wide tiles. At (512, 512, 1408) the double-
    buffered tiles and the float32 accumulator take about 9.5 MB of VMEM."""
    tm = 512 if m >= 512 else -(-m // 16) * 16
    return tm, _tile(k, 512, 1536), _tile(n, 512, 1536)


def _metadata(group_sizes: jax.Array, m: int, tm: int, visit_empty: bool):
    """(offsets (G+1,), group_ids, m_tile_ids (tiles_m + G - 1,)) and the
    number of grid steps: one per (group, row tile) pair that the group
    touches, in row order (megablox ``make_group_metadata``)."""
    G = group_sizes.shape[0]
    tiles_m = m // tm
    ends = jnp.cumsum(group_sizes).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    starts = offsets[:-1]
    rounded = (ends + tm - 1) // tm * tm - starts // tm * tm
    group_tiles = jnp.where(group_sizes == 0, 0, rounded) // tm
    if visit_empty:
        group_tiles = jnp.where(group_sizes == 0, 1, group_tiles)
    group_ids = jnp.repeat(
        jnp.arange(G, dtype=jnp.int32), group_tiles, total_repeat_length=tiles_m + G - 1
    )
    # A row tile is visited once by the group owning its first row and
    # once more by each group that starts inside it.
    starts_inside = (starts % tm != 0) & (group_sizes != 0)
    if visit_empty:
        starts_inside = starts_inside | (group_sizes == 0)
    tile_of_start = jnp.where(starts_inside, starts // tm, tiles_m)
    visits = jnp.zeros(tiles_m + 1, jnp.int32).at[tile_of_start].add(1)[:tiles_m] + 1
    m_tile_ids = jnp.repeat(
        jnp.arange(tiles_m, dtype=jnp.int32), visits, total_repeat_length=tiles_m + G - 1
    )
    return (offsets, group_ids, m_tile_ids), group_tiles.sum().astype(jnp.int32)


def _rows_of_group(offsets, g, m_tile, tm: int, width: int):
    row = m_tile * tm + lax.broadcasted_iota(jnp.int32, (tm, width), 0)
    return (row >= offsets[g]) & (row < offsets[g + 1])


def _pad_rows(x: jax.Array, m_pad: int) -> jax.Array:
    return x if x.shape[0] == m_pad else jnp.pad(x, ((0, m_pad - x.shape[0]), (0, 0)))


def gmm(
    lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
    transpose_rhs: bool = False, interpret: bool = False,
) -> jax.Array:
    """(m, k) rows sorted by group times each group's (k, n) matrix ->
    (m, n) in lhs's dtype, accumulated in float32."""
    m, k = lhs.shape
    G = rhs.shape[0]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = pick_tiles(m, k, n)
    m_pad = -(-m // tm) * tm
    meta, steps = _metadata(group_sizes.astype(jnp.int32), m_pad, tm, visit_empty=False)
    tiles_k = k // tk

    def kernel(offsets, group_ids, m_tile_ids, lhs_ref, rhs_ref, out_ref, acc_ref):
        grid_id, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        dims = (((1,), (1,)), ((), ())) if transpose_rhs else (((1,), (0,)), ((), ()))
        acc_ref[...] += lax.dot_general(
            lhs_ref[...], rhs_ref[...], dims, preferred_element_type=jnp.float32
        )

        @pl.when(k_i == tiles_k - 1)
        def _store():
            mask = _rows_of_group(offsets, group_ids[grid_id], m_tile_ids[grid_id], tm, tn)
            out_ref[...] = jnp.where(
                mask, acc_ref[...], out_ref[...].astype(jnp.float32)
            ).astype(out_ref.dtype)

    def lhs_map(n_i, grid_id, k_i, offsets, group_ids, m_tile_ids):
        return m_tile_ids[grid_id], k_i

    def rhs_map(n_i, grid_id, k_i, offsets, group_ids, m_tile_ids):
        if transpose_rhs:
            return group_ids[grid_id], n_i, k_i
        return group_ids[grid_id], k_i, n_i

    def out_map(n_i, grid_id, k_i, offsets, group_ids, m_tile_ids):
        return m_tile_ids[grid_id], n_i

    rhs_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)
    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m_pad, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map), pl.BlockSpec(rhs_block, rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            grid=(n // tn, steps, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="expert_gmm_t" if transpose_rhs else "expert_gmm",
    )
    out = call(*meta, _pad_rows(lhs, m_pad), rhs)[:m]
    routed = jnp.arange(m)[:, None] < meta[0][G]
    return jnp.where(routed, out, jnp.zeros((), out.dtype))


def tgmm(
    lhs: jax.Array, dout: jax.Array, group_sizes: jax.Array,
    out_dtype, interpret: bool = False,
) -> jax.Array:
    """Per group, lhs[rows]^T @ dout[rows]: (m, k), (m, n) -> (G, k, n),
    accumulated in float32; an empty group's block is 0."""
    m, k = lhs.shape
    n = dout.shape[1]
    G = group_sizes.shape[0]
    tm, tk, tn = pick_tiles(m, k, n)
    m_pad = -(-m // tm) * tm
    meta, steps = _metadata(group_sizes.astype(jnp.int32), m_pad, tm, visit_empty=True)

    def kernel(offsets, group_ids, m_tile_ids, lhs_ref, dout_ref, out_ref, acc_ref):
        grid_id = pl.program_id(2)
        g = group_ids[grid_id]
        prev = group_ids[jnp.where(grid_id > 0, grid_id - 1, 0)]

        @pl.when((grid_id == 0) | (prev != g))
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(offsets[g + 1] > offsets[g])
        def _accumulate():
            m_tile = m_tile_ids[grid_id]
            a = jnp.where(
                _rows_of_group(offsets, g, m_tile, tm, tk),
                lhs_ref[...].astype(jnp.float32), 0.0,
            ).swapaxes(0, 1)
            b = jnp.where(
                _rows_of_group(offsets, g, m_tile, tm, tn),
                dout_ref[...].astype(jnp.float32), 0.0,
            )
            acc_ref[...] += lax.dot(
                a.astype(lhs_ref.dtype), b.astype(dout_ref.dtype),
                preferred_element_type=jnp.float32,
            )

        last = grid_id == pl.num_programs(2) - 1
        nxt = group_ids[jnp.where(last, grid_id, grid_id + 1)]

        @pl.when(last | (nxt != g))
        def _store():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    def lhs_map(n_i, k_i, grid_id, offsets, group_ids, m_tile_ids):
        return m_tile_ids[grid_id], k_i

    def dout_map(n_i, k_i, grid_id, offsets, group_ids, m_tile_ids):
        return m_tile_ids[grid_id], n_i

    def out_map(n_i, k_i, grid_id, offsets, group_ids, m_tile_ids):
        return group_ids[grid_id], k_i, n_i

    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((G, k, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map), pl.BlockSpec((tm, tn), dout_map)],
            out_specs=pl.BlockSpec((None, tk, tn), out_map),
            grid=(n // tn, k // tk, steps),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="expert_gmm_tgmm",
    )
    return call(*meta, _pad_rows(lhs, m_pad), _pad_rows(dout, m_pad))


def _one_call_per_entry(fn):
    """``fn`` under vmap: each batch entry is its own call, in sequence."""
    f = jax.custom_batching.custom_vmap(fn)

    @f.def_vmap
    def _rule(axis_size, in_batched, *args):
        args = [
            a if b else jnp.broadcast_to(a, (axis_size, *a.shape))
            for a, b in zip(args, in_batched)
        ]
        return lax.map(lambda xs: f(*xs), tuple(args)), True

    return f


@functools.lru_cache(maxsize=None)
def grouped_matmul(interpret: bool):
    """The differentiable grouped product (lhs, rhs, group_sizes) -> out,
    running the kernels natively or in interpret mode."""
    fwd_gmm = _one_call_per_entry(functools.partial(gmm, interpret=interpret))
    bwd_gmm = _one_call_per_entry(
        functools.partial(gmm, transpose_rhs=True, interpret=interpret))

    @jax.custom_vjp
    def op(lhs, rhs, group_sizes):
        return fwd_gmm(lhs, rhs, group_sizes)

    def op_fwd(lhs, rhs, group_sizes):
        return fwd_gmm(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)

    def op_bwd(res, dout):
        lhs, rhs, group_sizes = res
        dlhs = bwd_gmm(dout, rhs, group_sizes)
        bwd_tgmm = _one_call_per_entry(
            functools.partial(tgmm, out_dtype=rhs.dtype, interpret=interpret))
        drhs = bwd_tgmm(lhs, dout, group_sizes)
        return dlhs, drhs, None

    op.defvjp(op_fwd, op_bwd)
    return op
