"""Pallas TPU kernel: fused Lloyd *update* (assign + deviation-accumulate).

The Lloyd iteration is the per-step K-means tax FedLite pays at the cut
layer: for every train step, every iteration re-reads the activations,
assigns them, and accumulates centroid statistics. The jnp path fuses the
assign into the scan body, but XLA still materializes a ``(chunk, L)``
one-hot and issues a second centroid read (the ``cents[codes]`` gather) per
scan step. This kernel does the whole iteration in ONE HBM sweep over X:

    codes[i]   = argmin_l ‖x_i − c_l‖²                      (MXU matmul)
    dsums[l]  += Σ_{i: codes_i=l} w_i · (x_i − c_l)         (MXU matmul)
    counts[l] += Σ_{i: codes_i=l} w_i

It works on the transposed activations Xᵀ (D, N) and (1, N) weights — the
lane-dense layout ``kmeans_assign.py`` describes. The one-hot exists only
in VREGs/VMEM; the codebook is VMEM-resident for the whole grid; the
accumulators are a single (L, D) + (L, 1) output block revisited by every
grid step (TPU grids are sequential, so the constant ``index_map`` makes
the output an accumulator — zeroed at ``program_id 0``). HBM traffic per
iteration: one read of X (+ the weights) and O(L·D) accumulator writes.

Numerics: statistics are accumulated as *deviations from the current
centroid* (``x − c_old``), matching the jnp scan in structure — a cluster
whose members all equal its centroid contributes an exactly-zero update
(the one-hot gather runs at HIGHEST precision, so it is exact), which the
FedLite ≡ SplitFed gradient-equivalence test depends on. Rows with weight 0
(padding) contribute exactly nothing. Empty clusters report ``counts == 0``
and the caller keeps the previous centroid.

Validated against ``ref.lloyd_update_ref`` in interpret mode on CPU;
compiled by Mosaic on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.kmeans_assign import (HIGHEST, codebook_operands,
                                         scores_and_codes)
from repro.kernels.pq_quantize import onehot_gather


def _update_kernel(xt_ref, w_ref, c_ref, ct_ref, cnorm_ref, lmask_ref,
                   dsums_ref, counts_ref):
    # zero the accumulators once; later grid steps revisit the same block
    @pl.when(pl.program_id(0) == 0)
    def _():
        dsums_ref[...] = jnp.zeros_like(dsums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    xt = xt_ref[...].astype(jnp.float32)            # (D, BN)
    w = w_ref[...].astype(jnp.float32)              # (1, BN)
    c = c_ref[...].astype(jnp.float32)              # (L, D)
    _, _, codes = scores_and_codes(xt, c, cnorm_ref[...], lmask_ref[...])
    onehot, zt = onehot_gather(codes, ct_ref[...].astype(jnp.float32),
                               c.shape[0])
    delta = xt - zt                                 # exact 0 on exact cover
    ohw = onehot * w                                # padded rows weigh 0
    dsums_ref[...] += jax.lax.dot_general(
        ohw, delta, (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)
    counts_ref[...] += jnp.sum(ohw, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def lloyd_update_kernel(xt: jax.Array, weights: jax.Array,
                        centroids: jax.Array, lmask: jax.Array, *,
                        block_n: int, interpret: bool = False):
    """xt: (D, N) with N % block_n == 0; weights: (1, N); centroids: (L, D);
    lmask: (L,) 1.0 = valid centroid.

    Returns (dsums (L, D) f32 = Σ onehot·(x − c_old), counts (L,) f32).
    """
    d, n = xt.shape
    l = centroids.shape[0]
    cnorm, lm = codebook_operands(centroids, lmask)
    dsums, counts = pl.pallas_call(
        _update_kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((d, block_n), lambda i: (0, i)),   # stream Xᵀ tiles
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((l, d), lambda i: (0, 0)),         # codebook resident
            pl.BlockSpec((d, l), lambda i: (0, 0)),
            pl.BlockSpec((l, 1), lambda i: (0, 0)),
            pl.BlockSpec((l, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((l, d), lambda i: (0, 0)),         # accumulators:
            pl.BlockSpec((l, 1), lambda i: (0, 0)),         # same block ∀ i
        ],
        out_shape=[
            jax.ShapeDtypeStruct((l, d), jnp.float32),
            jax.ShapeDtypeStruct((l, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xt, weights.astype(jnp.float32), centroids, centroids.T, cnorm, lm)
    return dsums, counts[:, 0]
