"""Grouped-query attention with RoPE/M-RoPE, and DeepSeek-V3's latent
attention (MLA), over four execution paths:

  * ``flash``: on a TPU, where the sequence tiles into the flash kernels'
    blocks and no window shorter than the sequence cuts it, the fused
    kernels of ``kernels/flash_attention.py`` (through
    ``kernels/ops.causal_attention``): forward and backward keep every
    score tile in VMEM and skip the tiles the mask drops whole. The same
    mask and precision as ``row_block``, which is its fallback.
  * ``row_block``: causal (optionally windowed) attention computed in query
    row-blocks via ``lax.scan`` — peak memory O(q_chunk · S_kv) instead of
    O(S²). The block body is wrapped in ``jax.checkpoint`` so the backward
    pass rematerializes per-block probabilities instead of storing them.
  * ``local``: exact sliding-window attention for long sequences — queries are
    reshaped into window-sized blocks that attend to (previous ‖ own) key
    blocks; compute is O(S · 2W) rather than O(S²).
  * ``decode``: one query token against a (possibly ring-buffered) KV cache.

KV caches are dicts {k, v, pos}; ``pos`` records the absolute position held
in each slot so windowed ring buffers and full caches share one code path.

MLA (``cfg.attn_kind == "mla"``, arXiv:2412.19437 §2.1.1, without the query
latent): q = x·W_q splits per head into q_nope and q_pe; [c_kv, k_pe] =
x·W_kva, c_kv is RMS-normalized and up-projected to [k_nope, v] per head;
q_pe and the single k_pe (shared by every head) are rotated on interleaved
pairs. Keys are [k_nope, k_pe] of ``head_dim`` and values ``v_head_dim``
wide, so the paths take the value width from v; the cache holds the
expanded per-head keys and values.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.ops import causal_attention, flash_block
from repro.models.layers import apply_norm, dense_init, norm_init, row
from repro.models.rope import apply_rope, apply_rope_interleaved, rope_angles
from repro.sharding import shard, shard_residual

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def attn_init(key, cfg, dtype):
    ks = jax.random.split(key, 4)
    if cfg.attn_kind == "mla":
        return _mla_init(ks, cfg, dtype)
    D, Q, KV = cfg.d_model, cfg.q_dim, cfg.kv_dim
    p = {
        "wq": dense_init(ks[0], D, Q, dtype),
        "wk": dense_init(ks[1], D, KV, dtype),
        "wv": dense_init(ks[2], D, KV, dtype),
        "wo": dense_init(ks[3], Q, D, dtype),
    }
    if cfg.use_bias:
        p["wq_b"] = jnp.zeros((Q,), dtype)
        p["wk_b"] = jnp.zeros((KV,), dtype)
        p["wv_b"] = jnp.zeros((KV,), dtype)
        p["wo_b"] = jnp.zeros((D,), dtype)
    return p


def _mla_init(ks, cfg, dtype):
    D, H, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    nope = cfg.head_dim - cfg.qk_rope_dim
    return {
        "wq": dense_init(ks[0], D, H * cfg.head_dim, dtype),
        "wkv_a": dense_init(ks[1], D, r + cfg.qk_rope_dim, dtype),
        "kv_norm": norm_init(r, "rmsnorm", dtype),
        "wkv_b": dense_init(ks[2], r, H * (nope + cfg.v_dim), dtype),
        "wo": dense_init(ks[3], H * cfg.v_dim, D, dtype),
    }


def _mla_project(p, x, cfg, positions):
    """x: (B, S, D) -> q, k (B,S,H,head_dim), v (B,S,H,v_head_dim)."""
    B, S, _ = x.shape
    H, r, rope = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_rope_dim
    nope = cfg.head_dim - rope
    angles = rope_angles(positions, rope, cfg.rope_theta)
    q = (x @ p["wq"]).reshape(B, S, H, cfg.head_dim)
    kv_a = x @ p["wkv_a"]
    c_kv = apply_norm(p["kv_norm"], kv_a[..., :r], "rmsnorm", cfg.norm_eps)
    k_pe = apply_rope_interleaved(kv_a[..., r:].reshape(B, S, 1, rope), angles)
    kv = (c_kv @ p["wkv_b"]).reshape(B, S, H, nope + cfg.v_dim)
    q = jnp.concatenate(
        [q[..., :nope], apply_rope_interleaved(q[..., nope:], angles)], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (B, S, H, rope))], -1)
    return q, k, kv[..., nope:]


def _project(p, x, cfg, angles):
    """x: (B, S, D) -> q (B,S,H,hd), k/v (B,S,Kv,hd) with RoPE applied."""
    B, S, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "wq_b" in p:
        q = q + row(p["wq_b"], q.ndim)
        k = k + row(p["wk_b"], k.ndim)
        v = v + row(p["wv_b"], v.ndim)
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    q = apply_rope(q, angles)
    k = apply_rope(k, angles)
    return q, k, v


# ---------------------------------------------------------------------------
# score computation (shared)
# ---------------------------------------------------------------------------

def _gqa_scores(q, k, scale):
    """q: (B,Sq,Kv,G,hd), k: (B,Skv,Kv,hd) -> (B,Kv,G,Sq,Skv) fp32."""
    return jnp.einsum("bqkgh,bskh->bkgqs", q, k,
                      preferred_element_type=jnp.float32) * scale


def _gqa_out(probs, v):
    """probs: (B,Kv,G,Sq,Skv), v: (B,Skv,Kv,hd) -> (B,Sq,Kv,G,hd)."""
    return jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(v.dtype), v)


def _mask(qpos, kpos, window: Optional[int]):
    """(Sq,) x (Skv,) -> (Sq, Skv) bool keep-mask: causal + sliding window."""
    m = qpos[:, None] >= kpos[None, :]
    if window is not None:
        m &= (qpos[:, None] - kpos[None, :]) < window
    m &= kpos[None, :] >= 0  # invalid / unwritten slots carry pos = -1
    return m


# ---------------------------------------------------------------------------
# path 1: row-block causal attention
# ---------------------------------------------------------------------------

def row_block_attention(q, k, v, qpos, kpos, *, window: Optional[int],
                        q_chunk: int, scale: float):
    """q: (B,Sq,H,hd), k: (B,Skv,Kv,hd), v: (B,Skv,Kv,vd), qpos: (Sq,),
    kpos: (Skv,) -> (B,Sq,H,vd)."""
    B, Sq, H, hd = q.shape
    Kv, vd = k.shape[2], v.shape[-1]
    G = H // Kv
    qg = q.reshape(B, Sq, Kv, G, hd)

    if Sq % q_chunk != 0:
        q_chunk = Sq  # small sequences: single block
    nb = Sq // q_chunk

    @jax.checkpoint
    def block(qb, qpb):
        s = _gqa_scores(qb, k, scale)
        keep = _mask(qpb, kpos, window)
        s = jnp.where(keep[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return _gqa_out(p, v)

    if nb == 1:
        out = block(qg, qpos)
    else:
        qb = qg.reshape(B, nb, q_chunk, Kv, G, hd).transpose(1, 0, 2, 3, 4, 5)
        qpb = qpos.reshape(nb, q_chunk)
        _, outs = jax.lax.scan(lambda c, x: (c, block(*x)), None, (qb, qpb))
        out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, Kv, G, vd)
    return out.reshape(B, Sq, H, vd)


def use_flash(seq_len: int, window: Optional[int]) -> bool:
    """Whether train and prefill attention over ``seq_len`` tokens take the
    flash kernels: on a TPU, where the sequence tiles into their blocks and
    the window, if any, spans it."""
    return (jax.default_backend() == "tpu"
            and flash_block(seq_len) is not None
            and (window is None or window >= seq_len))


# ---------------------------------------------------------------------------
# path 2: exact block-local sliding-window attention
# ---------------------------------------------------------------------------

def local_window_attention(q, k, v, qpos, kpos, *, window: int, scale: float):
    """Exact SWA when S % window == 0: block b attends to blocks {b-1, b}."""
    B, S, H, hd = q.shape
    Kv = k.shape[2]
    G = H // Kv
    W = window
    assert S % W == 0, "local attention requires seq divisible by window"
    nb = S // W

    qg = q.reshape(B, nb, W, Kv, G, hd)
    kb = k.reshape(B, nb, W, Kv, hd)
    vb = v.reshape(B, nb, W, Kv, v.shape[-1])
    # previous block (zeros + pos=-1 for block 0)
    prev = lambda x: jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)
    k2 = jnp.concatenate([prev(kb), kb], axis=2)  # (B, nb, 2W, Kv, hd)
    v2 = jnp.concatenate([prev(vb), vb], axis=2)
    qpb = qpos.reshape(nb, W)
    kpb = kpos.reshape(nb, W)
    kprev = jnp.concatenate([jnp.full((1, W), -1, kpos.dtype), kpb[:-1]], axis=0)
    kpb2 = jnp.concatenate([kprev, kpb], axis=1)  # (nb, 2W)

    @jax.checkpoint
    def block(qb, kb_, vb_, qp, kp):
        s = _gqa_scores(qb, kb_, scale)
        keep = _mask(qp, kp, W)
        s = jnp.where(keep[None, None, None], s, NEG_INF)
        return _gqa_out(jax.nn.softmax(s, axis=-1), vb_)

    out = jax.vmap(block, in_axes=(1, 1, 1, 0, 0), out_axes=1)(
        qg, k2, v2, qpb, kpb2)
    return out.reshape(B, S, H, v.shape[-1])


# ---------------------------------------------------------------------------
# path 3: single-token decode against a cache
# ---------------------------------------------------------------------------

def decode_attention(q, cache_k, cache_v, cache_pos, qpos, *,
                     window: Optional[int], scale: float):
    """q: (B,1,H,hd); cache_k/v: (B,Sc,Kv,hd); cache_pos: (Sc,); qpos scalar."""
    B, _, H, hd = q.shape
    Kv = cache_k.shape[2]
    G = H // Kv
    qg = q.reshape(B, 1, Kv, G, hd)
    s = _gqa_scores(qg, cache_k, scale)  # (B,Kv,G,1,Sc)
    keep = _mask(jnp.asarray(qpos)[None], cache_pos, window)  # (1, Sc)
    s = jnp.where(keep[None, None, None], s, NEG_INF)
    out = _gqa_out(jax.nn.softmax(s, axis=-1), cache_v)
    return out.reshape(B, 1, H, cache_v.shape[-1])


# ---------------------------------------------------------------------------
# full block: projections + attention + output
# ---------------------------------------------------------------------------

def init_attn_cache(cfg, batch: int, max_len: int, dtype):
    """Cache length = window size for SWA models (ring buffer), else max_len."""
    Sc = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    return {
        "k": jnp.zeros((batch, Sc, cfg.num_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, Sc, cfg.num_kv_heads, cfg.v_dim), dtype),
        "pos": jnp.full((Sc,), -1, jnp.int32),
    }


def apply_attention(p, x, cfg, positions, *, mode: str = "train",
                    cache=None, decode_pos=None):
    """Attention block.

    mode "train"/"prefill": x (B,S,D), positions (B,S) or (3,B,S) for M-RoPE.
      prefill additionally fills and returns the cache.
    mode "decode": x (B,1,D); decode_pos scalar absolute position; cache req'd.
    Returns (y, new_cache).
    """
    scale = 1.0 / math.sqrt(cfg.head_dim)
    if cfg.attn_kind == "mla":
        q, k, v = _mla_project(p, x, cfg, positions)
    else:
        angles = rope_angles(positions, cfg.head_dim, cfg.rope_theta,
                             cfg.mrope_sections)
        q, k, v = _project(p, x, cfg, angles)
    B, S = x.shape[:2]
    # token positions along the sequence (1D; batch-uniform by construction)
    pos1d = positions[0, 0] if positions.ndim == 3 else positions[0]

    new_cache = cache
    if mode == "decode":
        assert cache is not None
        Sc = cache["k"].shape[1]
        slot = jnp.mod(decode_pos, Sc)
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
        cpos = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], jnp.asarray(decode_pos, jnp.int32)[None], slot, axis=0)
        new_cache = {"k": ck, "v": cv, "pos": cpos}
        out = decode_attention(q, ck, cv, cpos, decode_pos,
                               window=cfg.sliding_window, scale=scale)
    else:
        if cfg.sliding_window and S > 2 * cfg.sliding_window and S % cfg.sliding_window == 0:
            out = local_window_attention(q, k, v, pos1d, pos1d,
                                         window=cfg.sliding_window, scale=scale)
        elif use_flash(S, cfg.sliding_window):
            out = causal_attention(q, k, v, pos1d, pos1d, scale=scale,
                                   window=cfg.sliding_window)
        else:
            out = row_block_attention(q, k, v, pos1d, pos1d,
                                      window=cfg.sliding_window,
                                      q_chunk=cfg.attn_q_chunk, scale=scale)
        if mode == "prefill":
            assert cache is not None
            Sc = cache["k"].shape[1]
            if Sc >= S:
                ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, 0, axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, 0, axis=1)
                cpos = jax.lax.dynamic_update_slice_in_dim(
                    cache["pos"], pos1d.astype(jnp.int32), 0, axis=0)
            else:  # windowed ring cache: keep the last Sc tokens, ring-aligned
                # slot invariant: position p lives in slot p % Sc, so later
                # decode writes (slot = pos % Sc) evict exactly the oldest token
                shift = S % Sc
                ck = jnp.roll(k[:, S - Sc:], shift, axis=1)
                cv = jnp.roll(v[:, S - Sc:], shift, axis=1)
                cpos = jnp.roll(pos1d[S - Sc:].astype(jnp.int32), shift, axis=0)
            new_cache = {"k": ck, "v": cv, "pos": cpos}

    y = out.reshape(B, S, -1) @ p["wo"]
    if "wo_b" in p:
        y = y + row(p["wo_b"], y.ndim)
    return shard_residual(y), new_cache
