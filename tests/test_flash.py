"""The flash attention kernels (interpret mode) against the row-block path,
and the dispatch between the two in ``models/attention.apply_attention``."""

import math
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.kernels import ops
from repro.kernels.flash_attention import FULL, PARTIAL, SKIP, block_tables
from repro.models import attention
from repro.models.attention import row_block_attention


def _mk(B, S, H, Kv, hd, vd, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (B, S, H, hd), dtype),
            jax.random.normal(ks[1], (B, S, Kv, hd), dtype),
            jax.random.normal(ks[2], (B, S, Kv, vd), dtype),
            jax.random.normal(ks[3], (B, S, H, vd), jnp.float32))


def _out_and_grads(fn, q, k, v, ct):
    out, vjp = jax.vjp(fn, q, k, v)
    return (out,) + vjp(ct.astype(out.dtype))


@pytest.mark.parametrize("B,S,H,Kv,hd,vd,window,offset,dtype", [
    (1, 256, 4, 2, 64, 64, None, 0, "float32"),     # GQA group 2
    (2, 256, 4, 1, 32, 32, None, 0, "float32"),     # one key head for four
    (1, 384, 2, 2, 192, 128, None, 0, "float32"),   # MLA widths, 3 x 3 tiles
    (1, 256, 4, 2, 64, 64, 256, 0, "float32"),      # window = S
    (1, 256, 4, 2, 64, 64, 1000, 0, "float32"),     # window > S
    (1, 256, 4, 2, 64, 64, None, 7, "float32"),     # positions from 7
    (1, 256, 6, 2, 128, 128, None, 0, "bfloat16"),  # the LM path's dtype
])
def test_flash_matches_rowblock(monkeypatch, B, S, H, Kv, hd, vd, window,
                                offset, dtype):
    """Output and the gradients of q, k and v, with 128-token blocks so the
    causal block skip engages."""
    monkeypatch.setattr(ops, "FLASH_BLOCK", 128)
    q, k, v, ct = _mk(B, S, H, Kv, hd, vd, jnp.dtype(dtype))
    pos = jnp.arange(S) + offset
    scale = 1.0 / math.sqrt(hd)
    got = _out_and_grads(lambda q, k, v: ops.causal_attention(
        q, k, v, pos, pos, scale=scale, window=window), q, k, v, ct)
    ref = _out_and_grads(lambda q, k, v: row_block_attention(
        q, k, v, pos, pos, window=window, q_chunk=S, scale=scale),
        q, k, v, ct)
    tol = 1e-5 if dtype == "float32" else 2e-2
    for g, r in zip(got, ref):
        assert g.dtype == r.dtype and g.shape == r.shape
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        assert np.abs(g - r).max() <= tol * np.abs(r).max()


def test_flash_causality(monkeypatch):
    """Later keys and values never reach earlier outputs."""
    monkeypatch.setattr(ops, "FLASH_BLOCK", 128)
    q, k, v, _ = _mk(1, 256, 2, 2, 32, 32, seed=5)
    pos = jnp.arange(256)
    run = lambda kk, vv: ops.causal_attention(q, kk, vv, pos, pos, scale=0.2)
    o1 = run(k, v)
    o2 = run(k.at[:, -1].add(50.0), v.at[:, -1].add(50.0))
    np.testing.assert_allclose(o1[:, :-1], o2[:, :-1], rtol=1e-5, atol=1e-6)


def test_block_tables_causal():
    """Positions 0..S-1 in 4 blocks: the diagonal is partial, below it full,
    above it skipped; a skipped tile fetches the block the grid holds."""
    pos = jnp.arange(512)
    kind, fetch_k, fetch_q = (np.asarray(t).reshape(4, 4)
                              for t in block_tables(pos, pos, 128, 128, None))
    i, j = np.indices((4, 4))
    expect = np.where(i == j, PARTIAL, np.where(i > j, FULL, SKIP))
    np.testing.assert_array_equal(kind, expect)
    np.testing.assert_array_equal(fetch_k, np.minimum(j, i))   # (i, j)
    np.testing.assert_array_equal(fetch_q, np.maximum(j, i))   # (j, i)


def test_block_tables_window_and_unwritten_keys():
    """A window of one block keeps the diagonal and the tile below it; keys
    at position -1 (unwritten slots) are never kept."""
    pos = jnp.arange(512)
    kind = np.asarray(block_tables(pos, pos, 128, 128, 128)[0]).reshape(4, 4)
    i, j = np.indices((4, 4))
    np.testing.assert_array_equal(
        kind, np.where(i == j, PARTIAL, np.where(i == j + 1, PARTIAL, SKIP)))
    kpos = jnp.where(pos < 128, -1, pos)
    kind = np.asarray(block_tables(pos, kpos, 128, 128, None)[0]).reshape(4, 4)
    assert (kind[:, 0] == SKIP).all()


@pytest.mark.parametrize("backend,S,window,fused", [
    ("tpu", 2048, None, True),
    ("tpu", 4096, 4096, True),     # StarCoder2's window spans the sequence
    ("tpu", 8192, None, True),
    ("tpu", 384, None, True),      # blocks of 384 tokens
    ("tpu", 100, None, False),     # no multiple of 128 divides S
    ("tpu", 2048, 1024, False),    # a window shorter than S
    ("cpu", 2048, None, False),
])
def test_dispatch(monkeypatch, backend, S, window, fused):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert attention.use_flash(S, window) is fused


TINY = ArchConfig(name="tiny", family="dense", num_layers=2, d_model=64,
                  vocab_size=64, num_heads=4, num_kv_heads=2, head_dim=32,
                  d_ff=64, dtype="float32", param_dtype="float32",
                  attn_q_chunk=64)


@pytest.mark.parametrize("cfg", [
    TINY,
    replace(TINY, attn_kind="mla", num_kv_heads=4, head_dim=48,
            kv_lora_rank=32, qk_rope_dim=16, v_head_dim=32),
], ids=["gqa", "mla"])
def test_apply_attention_takes_flash_on_tpu(monkeypatch, cfg):
    """With the backend read as a TPU (kernels still interpreted), train
    attention runs the flash kernels and gives the row-block path's output
    and parameter gradients."""
    S = 256
    params = attention.attn_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, S, cfg.d_model))
    positions = jnp.broadcast_to(jnp.arange(S), (2, S))

    def loss(p):
        y, _ = attention.apply_attention(p, x, cfg, positions)
        return (y * jnp.cos(x)).sum()

    ref = jax.value_and_grad(loss)(params)
    calls = []
    real = attention.causal_attention
    monkeypatch.setattr(ops, "_interpret_default", lambda: True)
    monkeypatch.setattr(ops, "FLASH_BLOCK", 128)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(attention, "causal_attention",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    got = jax.value_and_grad(loss)(params)
    assert calls
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5)
