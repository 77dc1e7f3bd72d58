"""Pallas TPU kernels: fused uniform scalar quantize + b-bit code pack/unpack.

The scalarq compressor's hot loop is three elementwise sweeps in the naive
path: (1) normalize + round to codes, (2) dequantize to the reconstruction,
(3) pack codes into b-bit words for the wire. The quantize kernel fuses
(1)+(2) — one HBM read of the activations, codes and reconstruction emitted
from the same registers — and the pack/unpack kernels turn the bit-twiddling
into a single VPU multiply-accumulate over a (BLOCK_N, 32/b) tile.

Packing layout: 32/b codes per little-endian uint32 word, code j occupying
bits [j·b, (j+1)·b). For b ∈ {1, 2, 4, 8, 16} (32 % b == 0) this is exactly
the LSB-first bit stream ``federated/wire.py`` writes with numpy, so device
packing and host packing are interchangeable (asserted in tests).

``lo``/``scale`` are whole-tensor reduction outputs computed by XLA outside
the kernel (a (1, 1) SMEM-friendly operand); the kernel matches the jnp
reference formula ``clip(round((x − lo)/scale), 0, 2^b − 1)`` exactly, so
interpret-mode parity with the "jnp" backend is bitwise.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _quantize_kernel(levels, x_ref, lo_ref, scale_ref, codes_ref, recon_ref):
    x = x_ref[...].astype(jnp.float32)              # (BN, BD)
    lo = lo_ref[0, 0]
    scale = scale_ref[0, 0]
    codes = jnp.clip(jnp.round((x - lo) / scale), 0, levels)
    codes_ref[...] = codes.astype(jnp.int32)
    recon_ref[...] = (lo + codes * scale).astype(recon_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "block_n", "block_d",
                                             "interpret"))
def scalar_quantize_kernel(x: jax.Array, lo: jax.Array, scale: jax.Array,
                           *, bits: int, block_n: int, block_d: int,
                           interpret: bool = False):
    """x: (N, D), N % block_n == 0 and D % block_d == 0; lo/scale: () f32
    tensor-wide range.

    The grid tiles rows AND columns: three (block_n, block_d) blocks (x,
    codes, recon), each double-buffered, must fit scoped VMEM whatever the
    row width — a whole-row block of a (200, 9216) cut does not.

    Returns (codes (N, D) int32 in [0, 2^bits), recon (N, D) f32).
    """
    n, d = x.shape
    levels = (1 << bits) - 1
    codes, recon = pl.pallas_call(
        functools.partial(_quantize_kernel, float(levels)),
        grid=(n // block_n, d // block_d),
        in_specs=[
            pl.BlockSpec((block_n, block_d), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, block_d), lambda i, j: (i, j)),
            pl.BlockSpec((block_n, block_d), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), jnp.int32),
            jax.ShapeDtypeStruct((n, d), jnp.float32),
        ],
        interpret=interpret,
    )(x, lo.reshape(1, 1).astype(jnp.float32),
      scale.reshape(1, 1).astype(jnp.float32))
    return codes, recon


def _pack_kernel(bits, codes_ref, words_ref):
    codes = codes_ref[...].astype(jnp.uint32)       # (BN, 32/b)
    per_word = codes.shape[-1]
    weights = (jnp.uint32(1) << (jnp.arange(per_word, dtype=jnp.uint32)
                                 * jnp.uint32(bits)))
    words_ref[...] = jnp.sum(codes * weights[None, :], axis=-1,
                             dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("bits", "block_n", "interpret"))
def pack_codes_kernel(codes: jax.Array, *, bits: int, block_n: int = 512,
                      interpret: bool = False) -> jax.Array:
    """codes: (N_words, 32/bits) int32 -> (N_words,) uint32 packed words."""
    n, per_word = codes.shape
    assert per_word * bits == 32, "pack kernel needs 32 % bits == 0"
    return pl.pallas_call(
        functools.partial(_pack_kernel, bits),
        grid=(n // block_n,),
        in_specs=[pl.BlockSpec((block_n, per_word), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_n,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.uint32),
        interpret=interpret,
    )(codes)


def _unpack_kernel(bits, words_ref, codes_ref):
    words = words_ref[...].astype(jnp.uint32)       # (BN,)
    per_word = codes_ref.shape[-1]
    shifts = (jnp.arange(per_word, dtype=jnp.uint32) * jnp.uint32(bits))
    mask = jnp.uint32((1 << bits) - 1)
    codes_ref[...] = ((words[:, None] >> shifts[None, :]) & mask
                      ).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("bits", "block_n", "interpret"))
def unpack_codes_kernel(words: jax.Array, *, bits: int, block_n: int = 512,
                        interpret: bool = False) -> jax.Array:
    """words: (N_words,) uint32 -> (N_words, 32/bits) int32 codes."""
    n = words.shape[0]
    per_word = 32 // bits
    return pl.pallas_call(
        functools.partial(_unpack_kernel, bits),
        grid=(n // block_n,),
        in_specs=[pl.BlockSpec((block_n,), lambda i: (i,))],
        out_specs=pl.BlockSpec((block_n, per_word), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, per_word), jnp.int32),
        interpret=interpret,
    )(words)
