"""Pallas TPU kernel: fused PQ quantize-forward (assign + gather + residual).

The naive forward does three HBM sweeps over the activations: (1) distance/
argmin, (2) centroid gather to build z̃, (3) residual z − z̃ for the
gradient-correction term. This kernel fuses them: for each (D, BLOCK_N)
tile of the transposed activations the codebook is VMEM-resident, the
assignment is computed on the MXU, and z̃ᵀ and (z − z̃)ᵀ are emitted from
the same registers — one read + two writes per element total. The layout
(points on lanes) is the one ``kmeans_assign.py`` describes.

The gather from the VMEM codebook is expressed as a one-hot (D, L) @
(L, BLOCK_N) matmul — on TPU this is far faster than a row-gather because
it rides the MXU and avoids scalar addressing. It runs at HIGHEST precision
so the gathered centroid is exact (an exactly-covered point gets an
exactly-zero residual on the chip as in interpret mode).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.kmeans_assign import (HIGHEST, codebook_operands,
                                         scores_and_codes)


def onehot_gather(codes, ct, num_clusters):
    """(onehot (L, BN) f32, z̃ᵀ = Cᵀ·onehot (D, BN)) for (1, BN) codes."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (num_clusters,
                                                codes.shape[1]), 0)
    onehot = (iota == codes).astype(jnp.float32)
    zt = jax.lax.dot_general(ct, onehot, (((1,), (0,)), ((), ())),
                             precision=HIGHEST,
                             preferred_element_type=jnp.float32)
    return onehot, zt


def _fused_kernel(xt_ref, c_ref, ct_ref, cnorm_ref, lmask_ref,
                  zt_ref, resid_ref, codes_ref):
    xt = xt_ref[...].astype(jnp.float32)            # (D, BN)
    c = c_ref[...].astype(jnp.float32)              # (L, D)
    _, _, codes = scores_and_codes(xt, c, cnorm_ref[...], lmask_ref[...])
    codes_ref[...] = codes
    _, zt = onehot_gather(codes, ct_ref[...].astype(jnp.float32), c.shape[0])
    zt_ref[...] = zt.astype(zt_ref.dtype)
    resid_ref[...] = (xt - zt).astype(resid_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def pq_quantize_kernel(xt: jax.Array, centroids: jax.Array, lmask: jax.Array,
                       *, block_n: int, interpret: bool = False):
    """xt: (D, N), N % block_n == 0; centroids (L, D); lmask (L,).

    Returns (z̃ᵀ (D, N) xt.dtype, residualᵀ (D, N) f32, codes (1, N) int32).
    """
    d, n = xt.shape
    l = centroids.shape[0]
    cnorm, lm = codebook_operands(centroids, lmask)
    return pl.pallas_call(
        _fused_kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((d, block_n), lambda i: (0, i)),
            pl.BlockSpec((l, d), lambda i: (0, 0)),
            pl.BlockSpec((d, l), lambda i: (0, 0)),
            pl.BlockSpec((l, 1), lambda i: (0, 0)),
            pl.BlockSpec((l, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((d, block_n), lambda i: (0, i)),
            pl.BlockSpec((d, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d, n), xt.dtype),
            jax.ShapeDtypeStruct((d, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
        ],
        interpret=interpret,
    )(xt, centroids, centroids.T, cnorm, lm)
