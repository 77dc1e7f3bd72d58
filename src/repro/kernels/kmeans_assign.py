"""Pallas TPU kernel: K-means assignment (the PQ hot spot).

For a block of subvectors X ∈ R^{BN×D} and a codebook C ∈ R^{L×D}, computes

    codes[i]  = argmin_l ‖x_i − c_l‖²  = argmax_l (2·x_i·c_l − ‖c_l‖²)
    sqdist[i] = ‖x_i‖² − max_l (...)

Layout (v5e): the kernel works on the TRANSPOSED operand Xᵀ ∈ R^{D×N}, so
the point axis rides the 128 lanes and the subvector width D (8 for every
paper/assigned config) the sublanes. A (BN, 8) row-major tile would fill 8
of 128 lanes, and XLA would pad every such HBM operand 16x; a (8, BN) tile
is dense. Per-point results come out as (1, N) rows, which also keeps every
operand 2-D — a 1-D (N,) block must match XLA's 1024-element tiling, and
under ``vmap`` its batch axis would leave an illegal (1, BN) tail block.

  * the codebook lives in VMEM for the whole grid (L ≤ 1024, D ≤ 128);
  * Xᵀ is streamed through VMEM in (D, BLOCK_N) tiles — one HBM pass;
  * the cross-term rides the MXU as a (L×D)·(D×BLOCK_N) matmul in fp32;
    the argmax is a sublane reduction over L;
  * BLOCK_N is a multiple of 128 lanes (or the whole padded N); L is
    zero-padded to a sublane multiple by the ops.py wrapper, padding rows
    masked with -inf.

Validated against ``ref.py`` in interpret mode on CPU; compiled by Mosaic
on TPU (compile-checked for v5e in tests/test_tpu_compile.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -1e30
HIGHEST = jax.lax.Precision.HIGHEST


def scores_and_codes(xt, c, cnorm, lmask):
    """Shared kernel-body prologue: masked scores (L, BN) and the first
    maximizing centroid per point as a (1, BN) int32 row (``jnp.argmax``'s
    tie-break, written as sublane max/min reductions)."""
    scores = 2.0 * jax.lax.dot_general(
        c, xt, (((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32) - cnorm
    scores = jnp.where(lmask > 0, scores, NEG)
    best = jnp.max(scores, axis=0, keepdims=True)
    iota = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
    codes = jnp.min(jnp.where(scores == best, iota, scores.shape[0]),
                    axis=0, keepdims=True)
    return scores, best, codes


def _assign_kernel(xt_ref, c_ref, cnorm_ref, lmask_ref, codes_ref, dist_ref):
    xt = xt_ref[...].astype(jnp.float32)          # (D, BN)
    c = c_ref[...].astype(jnp.float32)            # (L, D)
    _, best, codes = scores_and_codes(xt, c, cnorm_ref[...], lmask_ref[...])
    codes_ref[...] = codes
    xnorm = jnp.sum(xt * xt, axis=0, keepdims=True)
    dist_ref[...] = jnp.maximum(xnorm - best, 0.0)


def codebook_operands(centroids: jax.Array, lmask: jax.Array):
    """(‖c‖² (L, 1), lmask (L, 1)) — the per-centroid columns every kernel
    of this family takes next to the codebook."""
    cnorm = jnp.sum(centroids.astype(jnp.float32) ** 2, axis=-1)[:, None]
    return cnorm, lmask.astype(jnp.float32)[:, None]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeans_assign_kernel(xt: jax.Array, centroids: jax.Array,
                         lmask: jax.Array, *, block_n: int,
                         interpret: bool = False):
    """xt: (D, N) with N % block_n == 0; centroids: (L, D); lmask: (L,).

    Returns (codes (1, N) int32, sqdist (1, N) f32).
    """
    d, n = xt.shape
    l = centroids.shape[0]
    cnorm, lm = codebook_operands(centroids, lmask)
    return pl.pallas_call(
        _assign_kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((d, block_n), lambda i: (0, i)),   # stream Xᵀ tiles
            pl.BlockSpec((l, d), lambda i: (0, 0)),         # codebook resident
            pl.BlockSpec((l, 1), lambda i: (0, 0)),
            pl.BlockSpec((l, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        interpret=interpret,
    )(xt, centroids, cnorm, lm)
