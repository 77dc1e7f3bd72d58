"""jit'd public wrappers around the Pallas kernels.

Handles layout and padding, and interpret-mode selection: ``interpret=True``
on non-TPU backends so the CPU container executes the kernel bodies in
Python for validation, compiled Mosaic kernels whenever the default backend
is a TPU (there is no path that interprets on the chip).

Every block a wrapper hands a kernel is legal for Mosaic: the PQ kernels
take the points on lanes (``x.T``, see ``kmeans_assign.py``) in blocks of a
multiple of 128 lanes; the scalar-quantize kernel takes (8k, 128m) tiles or
the whole (padded) dimension; the grouped matmuls take row tiles of 512 (or
all rows, padded to 8) and column tiles of 512 or the whole dimension; the
flash attention kernels take sequence blocks of a multiple of 128 and the
whole head width. The public signatures stay row-major (N, D).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import (block_tables, flash_attention_dkv,
                                           flash_attention_dq,
                                           flash_attention_fwd)
from repro.kernels.kmeans_assign import kmeans_assign_kernel
from repro.kernels.moe_gmm import (group_metadata, moe_gmm_kernel,
                                   moe_tgmm_kernel)
from repro.kernels.pq_quantize import pq_quantize_kernel

LANE = 128
SUBLANE = 8
# cap on the (L, block_n) f32 score tile a PQ kernel holds in VMEM (2 MiB)
SCORE_TILE_ELEMS = 1 << 19
# scalar-quantize column tile: three (256, 1024) f32/int32 blocks, double-
# buffered, take 6 MiB of scoped VMEM whatever the row width
SCALARQ_COL_BLOCK = 1024
# grouped matmul tiles: (512, 512) bf16 operand blocks with a (512, <=2048)
# f32 accumulator stay within the default scoped VMEM, double-buffered
GMM_ROW_BLOCK = 512
GMM_COL_BLOCK = 512
GMM_WHOLE_DIM = 2048
# flash attention's query and key block along the sequence
FLASH_BLOCK = 1024


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _pad_axis(x, block: int, axis: int):
    """Zero-pad ``axis`` up to a multiple of ``block``."""
    pad = (-x.shape[axis]) % block
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        x = jnp.pad(x, widths)
    return x


def _pad_rows(x, block):
    return _pad_axis(x, block, 0), x.shape[0]


def _pad_centroids(c):
    """Pad L to a sublane multiple; returns (padded codebook, (L_pad,) mask
    with 1.0 on the real centroids)."""
    l = c.shape[0]
    lmask = (jnp.arange(_round_up(l, SUBLANE)) < l).astype(jnp.float32)
    return _pad_axis(c, SUBLANE, 0), lmask


def _lane_block(n: int, num_clusters: int, block_n: int) -> int:
    """Points per grid step for the PQ kernels: a multiple of 128 lanes, no
    larger than ``block_n``, the score-tile cap, or N itself (rounded up)."""
    cap = max(LANE, SCORE_TILE_ELEMS // _round_up(num_clusters, SUBLANE))
    b = max(LANE, min(block_n, cap) // LANE * LANE)
    return min(b, _round_up(n, LANE))


def _points_on_lanes(x, block: int):
    """(N, D) -> zero-padded (D, N_pad), N_pad a multiple of ``block``."""
    return _pad_axis(x.T, block, 1)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeans_assign(x: jax.Array, centroids: jax.Array, *,
                  block_n: int = 2048, interpret: bool | None = None):
    """codes[i] = argmin_l ‖x_i − c_l‖²; also returns squared distances.

    x: (N, D) any float dtype; centroids: (L, D). Arbitrary N, L (padded
    internally).
    """
    interpret = _interpret_default() if interpret is None else interpret
    n = x.shape[0]
    block = _lane_block(n, centroids.shape[0], block_n)
    cp, lmask = _pad_centroids(centroids)
    codes, dist = kmeans_assign_kernel(_points_on_lanes(x, block), cp, lmask,
                                       block_n=block, interpret=interpret)
    return codes[0, :n], dist[0, :n]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def pq_quantize(x: jax.Array, centroids: jax.Array, *,
                block_n: int = 2048, interpret: bool | None = None):
    """Fused assign + dequantize + residual. Returns (z̃, residual, codes)."""
    interpret = _interpret_default() if interpret is None else interpret
    n = x.shape[0]
    block = _lane_block(n, centroids.shape[0], block_n)
    cp, lmask = _pad_centroids(centroids)
    zt, resid, codes = pq_quantize_kernel(_points_on_lanes(x, block), cp,
                                          lmask, block_n=block,
                                          interpret=interpret)
    return zt[:, :n].T, resid[:, :n].T, codes[0, :n]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def lloyd_update(x: jax.Array, centroids: jax.Array,
                 weights: jax.Array | None = None, *,
                 block_n: int = 2048, interpret: bool | None = None):
    """Fused Lloyd-iteration statistics: assign + deviation-accumulate in one
    HBM sweep (``kernels/lloyd_update.py``).

    x: (N, D) any float dtype; centroids: (L, D); weights: optional (N,)
    per-row weights (padding rows carry 0). Arbitrary N, L (padded
    internally; padded rows weigh zero, padded centroids are masked).
    Returns (dsums (L, D) f32 = Σ onehot·(x − c_old), counts (L,) f32).
    """
    from repro.kernels.lloyd_update import lloyd_update_kernel
    interpret = _interpret_default() if interpret is None else interpret
    n = x.shape[0]
    l = centroids.shape[0]
    if weights is None:
        weights = jnp.ones((n,), jnp.float32)
    block = _lane_block(n, l, block_n)
    wp = _pad_axis(weights.astype(jnp.float32)[None, :], block, 1)
    cp, lmask = _pad_centroids(centroids)
    dsums, counts = lloyd_update_kernel(_points_on_lanes(x, block), wp, cp,
                                        lmask, block_n=block,
                                        interpret=interpret)
    return dsums[:l], counts[:l]


@functools.partial(jax.jit, static_argnames=("bits", "block_n", "interpret"))
def scalar_quantize(x: jax.Array, lo: jax.Array, scale: jax.Array,
                    bits: int, *, block_n: int = 256,
                    interpret: bool | None = None):
    """Fused uniform b-bit quantize + dequantize (scalarq compressor hot
    loop). x: (N, D) any float dtype; lo/scale: () tensor-wide range.
    Returns (codes (N, D) int32, recon (N, D) f32).

    Blocks are (block_n, SCALARQ_COL_BLOCK) tiles — sublane/lane
    multiples, or the whole padded dimension when it is smaller — so VMEM
    use is bounded by the tile, not by the row width."""
    from repro.kernels.scalar_quant import scalar_quantize_kernel
    interpret = _interpret_default() if interpret is None else interpret
    n, d = x.shape
    bn = min(max(SUBLANE, block_n // SUBLANE * SUBLANE),
             _round_up(n, SUBLANE))
    bd = min(d, SCALARQ_COL_BLOCK)
    xp = _pad_axis(_pad_axis(x, bn, 0), bd, 1)
    codes, recon = scalar_quantize_kernel(xp, lo, scale, bits=bits,
                                          block_n=bn, block_d=bd,
                                          interpret=interpret)
    return codes[:n, :d], recon[:n, :d]


@functools.partial(jax.jit, static_argnames=("bits", "block_n", "interpret"))
def pack_codes(codes: jax.Array, bits: int, *, block_n: int = 512,
               interpret: bool | None = None) -> jax.Array:
    """Pack flat int32 codes at ``bits`` bits each into little-endian uint32
    words (32 % bits == 0). Bit-identical to the LSB-first numpy stream
    ``federated/wire.py`` writes. Returns (ceil(N·bits/32),) uint32."""
    from repro.kernels.scalar_quant import pack_codes_kernel
    assert 32 % bits == 0, "device packing needs bits in {1, 2, 4, 8, 16}"
    interpret = _interpret_default() if interpret is None else interpret
    per_word = 32 // bits
    flat = codes.reshape(-1)
    pad = (-flat.shape[0]) % per_word
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    mat = flat.reshape(-1, per_word)
    block_n = min(block_n, max(8, mat.shape[0]))
    matp, n = _pad_rows(mat, block_n)
    return pack_codes_kernel(matp, bits=bits, block_n=block_n,
                             interpret=interpret)[:n]


@functools.partial(jax.jit, static_argnames=("count", "bits", "block_n",
                                             "interpret"))
def unpack_codes(words: jax.Array, count: int, bits: int, *,
                 block_n: int = 512,
                 interpret: bool | None = None) -> jax.Array:
    """Inverse of ``pack_codes``: (N_words,) uint32 -> (count,) int32."""
    from repro.kernels.scalar_quant import unpack_codes_kernel
    assert 32 % bits == 0, "device unpacking needs bits in {1, 2, 4, 8, 16}"
    interpret = _interpret_default() if interpret is None else interpret
    block_n = min(block_n, max(8, words.shape[0]))
    wp, n = _pad_rows(words, block_n)
    codes = unpack_codes_kernel(wp, bits=bits, block_n=block_n,
                                interpret=interpret)
    return codes.reshape(-1)[:count]


def assign_impl_for_kmeans(x: jax.Array, centroids: jax.Array) -> jax.Array:
    """Adapter matching the ``Backend.assign`` signature in
    ``repro.core.kmeans`` (used by the built-in "pallas" backend)."""
    codes, _ = kmeans_assign(x, centroids)
    return codes


# ---------------------------------------------------------------------------
# causal flash attention
# ---------------------------------------------------------------------------

def flash_block(seq_len: int) -> int | None:
    """The flash kernels' sequence block for ``seq_len``: FLASH_BLOCK, or
    the largest multiple of 128 below it that divides ``seq_len``; None
    where no multiple of 128 does."""
    b = min(FLASH_BLOCK, seq_len) // LANE * LANE
    while b >= LANE and seq_len % b:
        b -= LANE
    return b if b >= LANE else None


def _flash_fwd(q, k, v, qpos, kpos, scale, window, block, interpret):
    kind, fetch_k, _ = block_tables(qpos, kpos, block, block, window)
    o, lse = flash_attention_fwd(kind, fetch_k, q, k, v, qpos[:, None],
                                 kpos[None, :], scale=scale, window=window,
                                 block_q=block, block_k=block,
                                 interpret=interpret)
    return o, (q, k, v, qpos, kpos, o, lse)


def _flash_bwd(scale, window, block, interpret, res, do):
    q, k, v, qpos, kpos, o, lse = res
    B, H, S, _ = q.shape
    kind, fetch_k, fetch_q = block_tables(qpos, kpos, block, block, window)
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1,
                 keepdims=True)
    static = dict(scale=scale, window=window, block_q=block, block_k=block,
                  interpret=interpret)
    dq = flash_attention_dq(kind, fetch_k, q, k, v, do, lse, di,
                            qpos[:, None], kpos[None, :], **static)
    dk, dv = flash_attention_dkv(kind, fetch_q, q, k, v, do,
                                 lse.reshape(B, H, 1, S),
                                 di.reshape(B, H, 1, S), qpos[None, :],
                                 kpos[:, None], **static)
    return dq, dk, dv, None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash(q, k, v, qpos, kpos, scale, window, block, interpret):
    return _flash_fwd(q, k, v, qpos, kpos, scale, window, block,
                      interpret)[0]


_flash.defvjp(_flash_fwd, _flash_bwd)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     qpos: jax.Array, kpos: jax.Array, *, scale: float,
                     window: int | None = None) -> jax.Array:
    """Masked softmax attention through the flash kernels
    (``kernels/flash_attention.py``), differentiable in q, k and v.

    q (B, S, H, hd), k (B, S, Kv, hd), v (B, S, Kv, vd) with H a multiple
    of Kv; qpos, kpos (S,) int positions. Keeps the pairs with qpos ≥ kpos,
    kpos ≥ 0 and, with a window, qpos − kpos < window: the mask of
    ``models/attention.row_block_attention``. S must have a ``flash_block``.
    Returns (B, S, H, vd) in q's dtype."""
    block = flash_block(q.shape[1])
    if block is None:
        raise ValueError(f"no flash block divides the sequence {q.shape[1]}")
    heads = lambda x: x.transpose(0, 2, 1, 3)
    o = _flash(heads(q), heads(k), heads(v), qpos.astype(jnp.int32),
               kpos.astype(jnp.int32), scale, window, block,
               _interpret_default())
    return heads(o)


# ---------------------------------------------------------------------------
# grouped matmul over the experts a layer holds
# ---------------------------------------------------------------------------

def _gmm_col_tile(dim: int) -> int:
    """A column tile: GMM_COL_BLOCK where it divides ``dim``, else the whole
    dimension up to GMM_WHOLE_DIM, else the largest dividing multiple of
    128 below GMM_COL_BLOCK."""
    if dim % GMM_COL_BLOCK == 0:
        return GMM_COL_BLOCK
    if dim <= GMM_WHOLE_DIM:
        return dim
    t = GMM_COL_BLOCK // LANE * LANE
    while t >= LANE and dim % t:
        t -= LANE
    if t < LANE:
        raise ValueError(f"no 128-multiple tile divides {dim}")
    return t


def _gmm_rows(lhs, group_sizes, visit_empty):
    """Pad the rows to the row tile; the grid's metadata for them."""
    tm = min(GMM_ROW_BLOCK, _round_up(lhs.shape[0], SUBLANE))
    lhs = _pad_axis(lhs, tm, 0)
    meta, num_tiles = group_metadata(group_sizes.astype(jnp.int32),
                                     lhs.shape[0], tm, visit_empty)
    return lhs, tm, meta, num_tiles


def _gmm(lhs, rhs, group_sizes, transpose_rhs, interpret):
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    lhs, tm, meta, num_tiles = _gmm_rows(lhs, group_sizes, False)
    out = moe_gmm_kernel(*meta, num_tiles, lhs, rhs,
                         tiling=(tm, _gmm_col_tile(k), _gmm_col_tile(n)),
                         transpose_rhs=transpose_rhs, interpret=interpret)
    return out[:m]


def _tgmm(lhs, rhs, group_sizes, out_dtype, interpret):
    lhs, tm, meta, num_tiles = _gmm_rows(lhs, group_sizes, True)
    rhs = _pad_axis(rhs, tm, 0)
    return moe_tgmm_kernel(
        *meta, num_tiles, lhs, rhs, num_groups=group_sizes.shape[0],
        tiling=(tm, _gmm_col_tile(lhs.shape[1]), _gmm_col_tile(rhs.shape[1])),
        out_dtype=out_dtype, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm_pallas(lhs, rhs, group_sizes, interpret):
    return _gmm(lhs, rhs, group_sizes, False, interpret)


def _gmm_pallas_fwd(lhs, rhs, group_sizes, interpret):
    return _gmm(lhs, rhs, group_sizes, False, interpret), \
        (lhs, rhs, group_sizes)


def _gmm_pallas_bwd(interpret, res, g):
    lhs, rhs, group_sizes = res
    g = g.astype(lhs.dtype)
    dlhs = _gmm(g, rhs, group_sizes, True, interpret)
    drhs = _tgmm(lhs, g, group_sizes, rhs.dtype, interpret)
    return dlhs, drhs, None


_gmm_pallas.defvjp(_gmm_pallas_fwd, _gmm_pallas_bwd)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   *, backend: str = "auto",
                   interpret: bool | None = None) -> jax.Array:
    """Row r of group g times rhs[g]: lhs (m, k) with its rows sorted by
    group, group_sizes (G,) int32 summing to at most m, rhs (G, k, n) ->
    (m, n) in lhs's dtype, accumulated in float32. Differentiable in lhs
    and rhs.

    Rows past the last group are zero on the ``jnp`` path
    (``jax.lax.ragged_dot``) and left unwritten by the Pallas kernels
    (``kernels/moe_gmm.py``), whose work follows the rows the groups hold:
    the caller masks them. ``auto`` takes the kernels on a TPU."""
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if backend == "jnp":
        return jax.lax.ragged_dot(
            lhs, rhs, group_sizes.astype(jnp.int32),
            preferred_element_type=jnp.float32).astype(lhs.dtype)
    if backend != "pallas":
        raise ValueError(f"grouped matmul backend {backend!r}")
    interpret = _interpret_default() if interpret is None else interpret
    return _gmm_pallas(lhs, rhs, group_sizes.astype(jnp.int32), interpret)
