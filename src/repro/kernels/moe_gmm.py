"""Pallas TPU kernels: grouped matrix products over the experts a layer holds.

The rows of the left operand are sorted by expert: group ``g`` owns rows
``[offsets[g], offsets[g + 1])`` and rows past the last group belong to
none (a dropless layer sizes its buffer for the worst case and fills only
the front). Two products cover a grouped linear layer and its gradient:

    moe_gmm_kernel:   out[rows_g] = lhs[rows_g] @ rhs[g]       (or rhs[g]ᵀ)
    moe_tgmm_kernel:  out[g]      = lhs[rows_g]ᵀ @ rhs[rows_g]

``moe_gmm_kernel`` is the forward and, with ``transpose_rhs``, the product
for the input's gradient; ``moe_tgmm_kernel`` is the weights' gradient. The
design is the one of JAX's megablox kernels
(``jax/experimental/pallas/ops/tpu/megablox/gmm.py``): a grid over the
(group, row tile) pairs that hold rows, found on the device and prefetched
as scalars, with a tile that straddles two groups visited once by each and
a store mask that keeps each visit to its own group's rows. The grid's
length is a traced number, so the work follows the rows routed here and not
the buffer's size. Rows in no group are never written by
``moe_gmm_kernel`` (the caller masks them) and never read by
``moe_tgmm_kernel``; an expert with no rows gets a zero gradient.

Each kernel's jitted wrapper holds only its ``pallas_call``, so a device
trace names the kernels ``moe_gmm_kernel.*`` and ``moe_tgmm_kernel.*``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def group_metadata(group_sizes: jax.Array, m: int, tm: int,
                   visit_empty: bool):
    """Which group and row tile each grid step works on.

    group_sizes: (G,) int32 with sum <= m; m a multiple of ``tm``. Returns
    (offsets (G+1,), group_ids (T,), tile_ids (T,)), num_tiles: step ``i <
    num_tiles`` computes group ``group_ids[i]`` on row tile
    ``tile_ids[i]``. A group whose rows start inside a tile shares that
    tile with the group before it; ``visit_empty`` gives an empty group one
    step (the weights' gradient must still write its zeros)."""
    g = group_sizes.shape[0]
    tiles_m = m // tm
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    tiles = (-(-ends // tm) - starts // tm).astype(jnp.int32)
    tiles = jnp.where(group_sizes == 0, 1 if visit_empty else 0, tiles)
    length = tiles_m + g - 1
    group_ids = jnp.repeat(jnp.arange(g, dtype=jnp.int32), tiles,
                           total_repeat_length=length)
    # every tile is visited once by the group its first row belongs to (or
    # none), and once more by each group that starts inside it
    shares = (starts % tm != 0) & (group_sizes > 0)
    if visit_empty:
        shares = shares | (group_sizes == 0)
    extra = jnp.zeros((tiles_m + 1,), jnp.int32).at[
        jnp.where(shares, starts // tm, tiles_m)].add(1)[:tiles_m]
    tile_ids = jnp.repeat(jnp.arange(tiles_m, dtype=jnp.int32), extra + 1,
                          total_repeat_length=length)
    return (offsets, group_ids, tile_ids), jnp.sum(tiles)


def _row_mask(offsets, group_ids, tile_ids, step, shape, tm):
    g = group_ids[step]
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + tile_ids[step] * tm
    return (rows >= offsets[g]) & (rows < offsets[g + 1])


def _gmm_body(offsets, group_ids, tile_ids, lhs_ref, rhs_ref, out_ref,
              acc_ref, *, tm, tiles_k, transpose_rhs):
    step, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(k_i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    contract = (((1,), (1,)), ((), ())) if transpose_rhs \
        else (((1,), (0,)), ((), ()))
    acc_ref[...] += jax.lax.dot_general(
        lhs_ref[...], rhs_ref[...], contract,
        preferred_element_type=jnp.float32)

    @pl.when(k_i == tiles_k - 1)
    def _():
        mask = _row_mask(offsets, group_ids, tile_ids, step, acc_ref.shape,
                         tm)
        out_ref[...] = jnp.where(mask, acc_ref[...],
                                 out_ref[...].astype(jnp.float32)
                                 ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tiling", "transpose_rhs",
                                             "interpret"))
def moe_gmm_kernel(offsets, group_ids, tile_ids, num_tiles, lhs, rhs, *,
                   tiling, transpose_rhs=False, interpret=False):
    """lhs (m, k) sorted by group; rhs (G, k, n), or (G, n, k) with
    ``transpose_rhs`` -> (m, n) in lhs's dtype, f32 accumulation."""
    tm, tk, tn = tiling
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tiles_k = k // tk
    if transpose_rhs:
        rhs_spec = pl.BlockSpec(
            (None, tn, tk), lambda j, i, kk, o, g, t: (g[i], j, kk))
    else:
        rhs_spec = pl.BlockSpec(
            (None, tk, tn), lambda j, i, kk, o, g, t: (g[i], kk, j))
    return pl.pallas_call(
        functools.partial(_gmm_body, tm=tm, tiles_k=tiles_k,
                          transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, num_tiles, tiles_k),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda j, i, kk, o, g, t: (t[i], kk)),
                rhs_spec,
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, i, kk, o, g, t: (t[i], j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k + m * n) * lhs.dtype.itemsize
            + rhs.size * rhs.dtype.itemsize),
        interpret=interpret,
    )(offsets, group_ids, tile_ids, lhs, rhs)


def _tgmm_body(offsets, group_ids, tile_ids, lhs_ref, rhs_ref, out_ref,
               acc_ref, *, tm):
    step = pl.program_id(2)
    last = pl.num_programs(2) - 1
    g = group_ids[step]
    first = (step == 0) | (group_ids[jnp.maximum(step - 1, 0)] != g)

    @pl.when(first)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(offsets[g + 1] > offsets[g])
    def _():
        f32 = jnp.float32
        keep_l = _row_mask(offsets, group_ids, tile_ids, step,
                           lhs_ref.shape, tm)
        keep_r = _row_mask(offsets, group_ids, tile_ids, step,
                           rhs_ref.shape, tm)
        lhs = jnp.where(keep_l, lhs_ref[...].astype(f32), 0.0)
        rhs = jnp.where(keep_r, rhs_ref[...].astype(f32), 0.0)
        acc_ref[...] += jax.lax.dot(
            lhs.swapaxes(0, 1).astype(lhs_ref.dtype),
            rhs.astype(rhs_ref.dtype), preferred_element_type=f32)

    @pl.when((step == last)
             | (group_ids[jnp.minimum(step + 1, last)] != g))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("num_groups", "tiling",
                                             "out_dtype", "interpret"))
def moe_tgmm_kernel(offsets, group_ids, tile_ids, num_tiles, lhs, rhs, *,
                    num_groups, tiling, out_dtype, interpret=False):
    """lhs (m, k), rhs (m, n), both sorted by group -> (G, k, n): per group
    the product of its rows, lhsᵀ @ rhs, f32 accumulation."""
    tm, tk, tn = tiling
    m, k = lhs.shape
    n = rhs.shape[1]
    return pl.pallas_call(
        functools.partial(_tgmm_body, tm=tm),
        out_shape=jax.ShapeDtypeStruct((num_groups, k, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, k // tk, num_tiles),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda j, kk, i, o, g, t: (t[i], kk)),
                pl.BlockSpec((tm, tn), lambda j, kk, i, o, g, t: (t[i], j)),
            ],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda j, kk, i, o, g, t: (g[i], kk, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k + m * n) * lhs.dtype.itemsize
            + num_groups * k * n * jnp.dtype(out_dtype).itemsize),
        interpret=interpret,
    )(offsets, group_ids, tile_ids, lhs, rhs)
