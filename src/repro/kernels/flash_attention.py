"""Pallas TPU kernels: causal flash attention, forward and backward.

Attention's score rows never reach HBM: each kernel walks the (query
block, key block) tiles of one head, holds what it needs of the tile in
VMEM and writes only its outputs.

    flash_attention_fwd:  o = softmax(q·kᵀ·scale, masked)·v, and the rows'
                          log-sum-exp, with the running max, sum and output
                          accumulator of the online softmax in VMEM scratch
    flash_attention_dq:   dq = Σ_kv ds·k · scale, scores recomputed per tile
                          from the saved log-sum-exp
    flash_attention_dkv:  dk = Σ_q dsᵀ·q · scale and dv = Σ_q pᵀ·do, summed
                          over the query heads of a key head and over the
                          query blocks in VMEM

with ds = p ⊙ (do·vᵀ − rowsum(o ⊙ do)). The mask is the one
``models/attention.row_block_attention`` applies: query position ≥ key
position, within the window where there is one, and key position ≥ 0. It
is computed from the positions themselves, on the tiles that straddle it.
``block_tables`` classifies every tile from the blocks' least and greatest
positions (no pair kept, some, all); the kernels skip a tile that keeps
nothing, compute no mask on one that keeps all, and their index maps fetch
no new block for a skipped tile (it repeats the neighbouring block's index,
which Pallas does not copy again). For positions 0..S−1 that is the causal
block skip: of n × n tiles, n(n+1)/2 run and n of them compute the mask.

Precision is the row-block path's: q, k, v and the cotangent enter the MXU
in their own dtype (bfloat16 on the LM path) with float32 accumulation,
the softmax statistics are float32, probabilities are cast to v's dtype
before p·v, and the scale multiplies the float32 scores.

Layout is head-major: q (B, H, S, hd), k (B, Kv, S, hd), v (B, Kv, S, vd),
with H a multiple of Kv (grouped-query attention: query head h reads key
head h // (H/Kv) through the index maps, so keys are never repeated per
query head) and vd free of hd (latent attention's value width). Blocks are
multiples of 128 along the sequence; hd and vd are whole blocks.

Each kernel's jitted wrapper holds only its ``pallas_call``, so a device
trace names the kernels ``flash_attention_fwd.*``, ``flash_attention_dq.*``
and ``flash_attention_dkv.*``. ``kernels/ops.causal_attention`` is the
differentiable entry point.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30   # the row-block path's masked score
LANES = 128
SKIP, PARTIAL, FULL = 0, 1, 2

NT = (((1,), (1,)), ((), ()))   # a·bᵀ
NN = (((1,), (0,)), ((), ()))   # a·b


def _fill(needed):
    """For each row of ``needed`` (n, m) bool: column j where it is needed,
    else the last needed column before j, else the first after it, else 0.
    A skipped tile so fetches the block the grid already holds."""
    m = needed.shape[1]
    col = jnp.arange(m, dtype=jnp.int32)
    prev = jax.lax.cummax(jnp.where(needed, col, -1), axis=1)
    nxt = jax.lax.cummin(jnp.where(needed, col, m), axis=1, reverse=True)
    return jnp.where(prev >= 0, prev, jnp.where(nxt < m, nxt, 0))


def block_tables(qpos, kpos, block_q: int, block_k: int, window):
    """Per tile (query block i, key block j), flat over (i, j): its kind
    (SKIP, PARTIAL, FULL) under the mask, the key block the forward and dq
    kernels fetch at (i, j), and the query block the dkv kernel fetches at
    (j, i), flat over (j, i). Bounds are from each block's least and
    greatest position, so a SKIP or FULL tile is exactly that."""
    qb = qpos.reshape(-1, block_q)
    kb = kpos.reshape(-1, block_k)
    qlo, qhi = qb.min(1)[:, None], qb.max(1)[:, None]
    klo, khi = kb.min(1)[None, :], kb.max(1)[None, :]
    none = (qhi < klo) | (khi < 0)
    every = (qlo >= khi) & (klo >= 0)
    if window is not None:
        none |= (qlo - khi) >= window
        every &= (qhi - klo) < window
    kind = jnp.where(none, SKIP, jnp.where(every, FULL, PARTIAL))
    needed = kind != SKIP
    return (kind.astype(jnp.int32).reshape(-1),
            _fill(needed).reshape(-1),
            _fill(needed.T).reshape(-1))


def _keep(qpos, kpos, window):
    keep = (qpos >= kpos) & (kpos >= 0)
    if window is not None:
        keep &= (qpos - kpos) < window
    return keep


def _lanes(x, width: int):
    """(r, LANES) with equal lanes -> (r, width)."""
    return jnp.tile(x, (1, pl.cdiv(width, LANES)))[:, :width]


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_body(kind_ref, fetch_ref, q_ref, k_ref, v_ref, qpos_ref, kpos_ref,
              o_ref, lse_ref, m_ref, l_ref, acc_ref, *, scale, window, nk):
    i, j = pl.program_id(2), pl.program_id(3)
    kind = kind_ref[i * nk + j]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(masked):
        s = jax.lax.dot_general(q_ref[...], k_ref[...], NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = jnp.where(_keep(qpos_ref[...], kpos_ref[...], window), s,
                          NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_next = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_next, s.shape[1]))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[...] = alpha * l_prev + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_next
        v = v_ref[...]
        pv = jax.lax.dot_general(p.astype(v.dtype), v, NN,
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = _lanes(alpha, pv.shape[1]) * acc_ref[...] + pv

    pl.when(kind == PARTIAL)(lambda: tile(True))
    pl.when(kind == FULL)(lambda: tile(False))

    @pl.when(j == nk - 1)
    def _emit():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] * _lanes(1.0 / l, acc_ref.shape[1])
                      ).astype(o_ref.dtype)
        lse_ref[...] = (m_ref[...] + jnp.log(l))[:, :1]


@functools.partial(jax.jit, static_argnames=("scale", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention_fwd(kind, fetch, q, k, v, qpos, kpos, *, scale, window,
                        block_q, block_k, interpret=False):
    """q (B,H,S,hd), k (B,Kv,S,hd), v (B,Kv,S,vd); qpos (S,1) and kpos (1,S)
    int32; ``kind``/``fetch`` from ``block_tables``. Returns o (B,H,S,vd) in
    q's dtype and the rows' log-sum-exp (B,H,S,1) float32."""
    B, H, S, hd = q.shape
    Kv, vd = k.shape[1], v.shape[-1]
    G = H // Kv
    nq, nk = S // block_q, S // block_k

    def q_map(b, h, i, j, kind, fetch):
        return b, h, i, 0

    def kv_map(b, h, i, j, kind, fetch):
        return b, h // G, fetch[i * nk + j], 0

    return pl.pallas_call(
        functools.partial(_fwd_body, scale=scale, window=window, nk=nk),
        out_shape=(jax.ShapeDtypeStruct((B, H, S, vd), q.dtype),
                   jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, nq, nk),
            in_specs=[
                pl.BlockSpec((None, None, block_q, hd), q_map),
                pl.BlockSpec((None, None, block_k, hd), kv_map),
                pl.BlockSpec((None, None, block_k, vd), kv_map),
                pl.BlockSpec((block_q, 1),
                             lambda b, h, i, j, kind, fetch: (i, 0)),
                pl.BlockSpec((1, block_k), lambda b, h, i, j, kind, fetch:
                             (0, fetch[i * nk + j])),
            ],
            out_specs=[
                pl.BlockSpec((None, None, block_q, vd), q_map),
                pl.BlockSpec((None, None, block_q, 1), q_map),
            ],
            scratch_shapes=[pltpu.VMEM((block_q, LANES), jnp.float32),
                            pltpu.VMEM((block_q, LANES), jnp.float32),
                            pltpu.VMEM((block_q, vd), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="flash_attention_fwd",
        interpret=interpret,
    )(kind, fetch, q, k, v, qpos, kpos)


# ---------------------------------------------------------------------------
# backward: dq
# ---------------------------------------------------------------------------

def _dq_body(kind_ref, fetch_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
             di_ref, qpos_ref, kpos_ref, dq_ref, acc_ref, *, scale, window,
             nk):
    i, j = pl.program_id(2), pl.program_id(3)
    kind = kind_ref[i * nk + j]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(masked):
        k = k_ref[...]
        s = jax.lax.dot_general(q_ref[...], k, NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = jnp.where(_keep(qpos_ref[...], kpos_ref[...], window), s,
                          NEG_INF)
        p = jnp.exp(s - lse_ref[...])
        dp = jax.lax.dot_general(do_ref[...], v_ref[...], NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[...])
        acc_ref[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, NN, preferred_element_type=jnp.float32)

    pl.when(kind == PARTIAL)(lambda: tile(True))
    pl.when(kind == FULL)(lambda: tile(False))

    @pl.when(j == nk - 1)
    def _emit():
        dq_ref[...] = (acc_ref[...] * scale).astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention_dq(kind, fetch, q, k, v, do, lse, di, qpos, kpos, *,
                       scale, window, block_q, block_k, interpret=False):
    """dq (B,H,S,hd) in q's dtype; do (B,H,S,vd), lse and di (B,H,S,1)."""
    B, H, S, hd = q.shape
    Kv, vd = k.shape[1], v.shape[-1]
    G = H // Kv
    nq, nk = S // block_q, S // block_k

    def q_map(b, h, i, j, kind, fetch):
        return b, h, i, 0

    def kv_map(b, h, i, j, kind, fetch):
        return b, h // G, fetch[i * nk + j], 0

    return pl.pallas_call(
        functools.partial(_dq_body, scale=scale, window=window, nk=nk),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, nq, nk),
            in_specs=[
                pl.BlockSpec((None, None, block_q, hd), q_map),
                pl.BlockSpec((None, None, block_k, hd), kv_map),
                pl.BlockSpec((None, None, block_k, vd), kv_map),
                pl.BlockSpec((None, None, block_q, vd), q_map),
                pl.BlockSpec((None, None, block_q, 1), q_map),
                pl.BlockSpec((None, None, block_q, 1), q_map),
                pl.BlockSpec((block_q, 1),
                             lambda b, h, i, j, kind, fetch: (i, 0)),
                pl.BlockSpec((1, block_k), lambda b, h, i, j, kind, fetch:
                             (0, fetch[i * nk + j])),
            ],
            out_specs=pl.BlockSpec((None, None, block_q, hd), q_map),
            scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="flash_attention_dq",
        interpret=interpret,
    )(kind, fetch, q, k, v, do, lse, di, qpos, kpos)


# ---------------------------------------------------------------------------
# backward: dk, dv
# ---------------------------------------------------------------------------

def _dkv_body(kind_ref, fetch_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
              di_ref, qpos_ref, kpos_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
              scale, window, nk, groups, nq):
    j, g, i = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    kind = kind_ref[i * nk + j]

    @pl.when((g == 0) & (i == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def tile(masked):
        q, do = q_ref[...], do_ref[...]
        # transposed scores (key rows, query lanes): the rows' statistics
        # arrive as (1, block_q) lane vectors
        s = jax.lax.dot_general(k_ref[...], q, NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = jnp.where(_keep(qpos_ref[...], kpos_ref[...], window), s,
                          NEG_INF)
        p = jnp.exp(s - lse_ref[...])
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, NN, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v_ref[...], do, NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[...])
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, NN, preferred_element_type=jnp.float32)

    pl.when(kind == PARTIAL)(lambda: tile(True))
    pl.when(kind == FULL)(lambda: tile(False))

    @pl.when((g == groups - 1) & (i == nq - 1))
    def _emit():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention_dkv(kind, fetch_q, q, k, v, do, lse_row, di_row,
                        qpos_row, kpos_col, *, scale, window, block_q,
                        block_k, interpret=False):
    """dk (B,Kv,S,hd), dv (B,Kv,S,vd) in k's and v's dtypes; lse_row and
    di_row (B,H,1,S), qpos_row (1,S), kpos_col (S,1); ``fetch_q`` is
    ``block_tables``' query-block table, flat over (j, i)."""
    B, H, S, hd = q.shape
    Kv, vd = k.shape[1], v.shape[-1]
    G = H // Kv
    nq, nk = S // block_q, S // block_k

    def q_map(b, kh, j, g, i, kind, fetch):
        return b, kh * G + g, fetch[j * nq + i], 0

    def row_map(b, kh, j, g, i, kind, fetch):
        return b, kh * G + g, 0, fetch[j * nq + i]

    def kv_map(b, kh, j, g, i, kind, fetch):
        return b, kh, j, 0

    return pl.pallas_call(
        functools.partial(_dkv_body, scale=scale, window=window, nk=nk,
                          groups=G, nq=nq),
        out_shape=(jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, Kv, nk, G, nq),
            in_specs=[
                pl.BlockSpec((None, None, block_q, hd), q_map),
                pl.BlockSpec((None, None, block_k, hd), kv_map),
                pl.BlockSpec((None, None, block_k, vd), kv_map),
                pl.BlockSpec((None, None, block_q, vd), q_map),
                pl.BlockSpec((None, None, 1, block_q), row_map),
                pl.BlockSpec((None, None, 1, block_q), row_map),
                pl.BlockSpec((1, block_q), lambda b, kh, j, g, i, kind, fetch:
                             (0, fetch[j * nq + i])),
                pl.BlockSpec((block_k, 1),
                             lambda b, kh, j, g, i, kind, fetch: (j, 0)),
            ],
            out_specs=[pl.BlockSpec((None, None, block_k, hd), kv_map),
                       pl.BlockSpec((None, None, block_k, vd), kv_map)],
            scratch_shapes=[pltpu.VMEM((block_k, hd), jnp.float32),
                            pltpu.VMEM((block_k, vd), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary")),
        name="flash_attention_dkv",
        interpret=interpret,
    )(kind, fetch_q, q, k, v, do, lse_row, di_row, qpos_row, kpos_col)
