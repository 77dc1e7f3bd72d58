"""DeepSeek-V3's blocks at small sizes on the CPU, against plain float32
formulations written here: the grouped-matmul kernels and their VJP (in
interpret mode) against ``jax.lax.ragged_dot``; latent attention (MLA) with
a value width other than the query/key width; RoPE on interleaved pairs;
the dropless expert layer over a share of the experts — the shares of a
layer add up to the uncut layer, and no token is dropped when every token
picks one expert."""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig, get_arch
from repro.kernels import ops
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models.rope import apply_rope_interleaved, rope_angles

HIGHEST = jax.lax.Precision.HIGHEST


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    err = np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)
    assert err < tol, err


# ---------------------------------------------------------------------------
# grouped matmul
# ---------------------------------------------------------------------------

GMM_CASES = [
    # rows, k, n, group sizes (the rows past their sum are in no group)
    (200, 64, 48, [30, 0, 70, 17]),
    (1124, 256, 384, [300, 0, 512, 100, 0]),    # groups straddle 512-tiles
    (96, 32, 16, [96, 0, 0]),                   # every row in one group
    (64, 32, 16, [0, 0]),                       # no row in any group
]


@pytest.mark.parametrize("m,k,n,sizes", GMM_CASES)
def test_grouped_matmul_kernels_and_vjp_match_ragged_dot(m, k, n, sizes):
    ks = jax.random.split(jax.random.PRNGKey(m), 3)
    gs = jnp.asarray(sizes, jnp.int32)
    live = (jnp.arange(m) < sum(sizes))[:, None]
    lhs = jax.random.normal(ks[0], (m, k), jnp.float32)
    rhs = jax.random.normal(ks[1], (len(sizes), k, n), jnp.float32)
    ct = jax.random.normal(ks[2], (m, n), jnp.float32)

    def loss(fn):
        return lambda a, b: jnp.sum(jnp.where(live, fn(a, b), 0.0) * ct)

    kernel = lambda a, b: ops.grouped_matmul(a, b, gs, backend="pallas",
                                             interpret=True)
    plain = lambda a, b: jax.lax.ragged_dot(a, b, gs, precision=HIGHEST)
    got = jnp.where(live, kernel(lhs, rhs), 0.0)
    want = plain(lhs, rhs)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    dl, dr = jax.grad(loss(kernel), (0, 1))(lhs, rhs)
    wl, wr = jax.grad(loss(plain), (0, 1))(lhs, rhs)
    np.testing.assert_allclose(jnp.where(live, dl, 0.0), wl, rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(dr, wr, rtol=1e-5, atol=1e-4)
    # the jnp backend is ragged_dot itself
    np.testing.assert_allclose(
        ops.grouped_matmul(lhs, rhs, gs, backend="jnp"), want, rtol=1e-5,
        atol=1e-4)


@pytest.mark.parametrize("dim,tile", [(2048, 512), (1408, 1408),
                                      (2816, 256), (3200, 128),
                                      (2049, None)])
def test_grouped_matmul_column_tile(dim, tile):
    """512 where it divides, else the whole dimension up to 2048, else the
    largest dividing multiple of 128; a dimension with none is refused."""
    if tile is None:
        with pytest.raises(ValueError):
            ops._gmm_col_tile(dim)
    else:
        assert ops._gmm_col_tile(dim) == tile


def test_grouped_matmul_bf16_accumulates_in_f32():
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    gs = jnp.asarray([40, 88], jnp.int32)
    lhs = jax.random.normal(ks[0], (128, 256), jnp.bfloat16)
    rhs = jax.random.normal(ks[1], (2, 256, 128), jnp.bfloat16)
    got = ops.grouped_matmul(lhs, rhs, gs, backend="pallas", interpret=True)
    assert got.dtype == jnp.bfloat16
    want = jax.lax.ragged_dot(lhs.astype(jnp.float32),
                              rhs.astype(jnp.float32), gs, precision=HIGHEST)
    _close(got, want, 1e-2)


# ---------------------------------------------------------------------------
# RoPE on interleaved pairs and MLA
# ---------------------------------------------------------------------------

def test_interleaved_rope_rotates_each_pair():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 8))
    pos = jnp.arange(5)[None]
    got = np.asarray(apply_rope_interleaved(x, rope_angles(pos, 8, 100.0)))
    xn = np.asarray(x)
    for s in range(5):
        for i in range(4):
            a = s * 100.0 ** (-2 * i / 8)
            c, sn = math.cos(a), math.sin(a)
            x1, x2 = xn[0, s, :, 2 * i], xn[0, s, :, 2 * i + 1]
            np.testing.assert_allclose(got[0, s, :, 2 * i], x1 * c - x2 * sn,
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(got[0, s, :, 2 * i + 1],
                                       x1 * sn + x2 * c, rtol=1e-5, atol=1e-5)


MLA_CFG = ArchConfig(
    name="mla_test", family="moe", num_layers=2, d_model=64, vocab_size=64,
    num_heads=4, num_kv_heads=4, head_dim=24, attn_kind="mla",
    kv_lora_rank=16, qk_rope_dim=8, v_head_dim=40, rope_theta=1000.0,
    d_ff=64, attn_q_chunk=8)


def _mla_plain(p, x, cfg):
    """Latent attention head by head, in numpy float64."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), p)
    x = np.asarray(x, np.float64)
    B, S, _ = x.shape
    H, r, rope, vd = (cfg.num_heads, cfg.kv_lora_rank, cfg.qk_rope_dim,
                      cfg.v_head_dim)
    nope = cfg.head_dim - rope
    q = (x @ p["wq"]).reshape(B, S, H, nope + rope)
    kv_a = x @ p["wkv_a"]
    c = kv_a[..., :r]
    c = c / np.sqrt(np.mean(c * c, -1, keepdims=True) + cfg.norm_eps) \
        * p["kv_norm"]["scale"]
    kv = (c @ p["wkv_b"]).reshape(B, S, H, nope + vd)

    def rot(v, s):      # pairs (2i, 2i+1) by s * theta^(-2i/rope)
        out = v.copy()
        for i in range(rope // 2):
            a = s * cfg.rope_theta ** (-2 * i / rope)
            c_, s_ = math.cos(a), math.sin(a)
            out[..., 2 * i] = v[..., 2 * i] * c_ - v[..., 2 * i + 1] * s_
            out[..., 2 * i + 1] = v[..., 2 * i] * s_ + v[..., 2 * i + 1] * c_
        return out

    out = np.zeros((B, S, H, vd))
    for b in range(B):
        kpe = np.stack([rot(kv_a[b, s, r:], s) for s in range(S)])
        for h in range(H):
            qh = np.stack([np.concatenate([q[b, s, h, :nope],
                                           rot(q[b, s, h, nope:], s)])
                           for s in range(S)])
            kh = np.concatenate([kv[b, :, h, :nope], kpe], -1)
            sc = qh @ kh.T / math.sqrt(cfg.head_dim)
            sc = np.where(np.tril(np.ones((S, S), bool)), sc, -np.inf)
            pr = np.exp(sc - sc.max(-1, keepdims=True))
            pr /= pr.sum(-1, keepdims=True)
            out[b, :, h] = pr @ kv[b, :, h, nope:]
    return out.reshape(B, S, H * vd) @ p["wo"]


def test_mla_with_value_width_unlike_query_width():
    cfg = MLA_CFG
    assert cfg.v_dim != cfg.head_dim
    p = attn_mod.attn_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    p["kv_norm"]["scale"] = 1.0 + 0.1 * jax.random.normal(
        jax.random.PRNGKey(2), (cfg.kv_lora_rank,))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(32), (2, 32))
    with jax.default_matmul_precision("highest"):
        y, _ = attn_mod.apply_attention(p, x, cfg, pos)
    assert y.shape == x.shape
    _close(y, _mla_plain(p, x, cfg), 1e-5)


# ---------------------------------------------------------------------------
# the dropless expert layer over a share of the experts
# ---------------------------------------------------------------------------

MOE_CFG = ArchConfig(
    name="moe_test", family="moe", num_layers=2, d_model=32, vocab_size=64,
    num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64, num_experts=16,
    experts_per_token=4, moe_layer="dropless", moe_d_ff=24,
    num_shared_experts=2, routed_scaling=2.5)


def _layer_params(cfg, key):
    p = moe_mod.dropless_init(key, cfg, jnp.float32)
    p["router_bias"] = 0.02 * jax.random.normal(jax.random.fold_in(key, 7),
                                                (cfg.num_experts,))
    return p


def _share(p, cfg, offset, held):
    sl = lambda w: w[offset:offset + held]
    q = dict(p, we_gate=sl(p["we_gate"]), we_up=sl(p["we_up"]),
             we_down=sl(p["we_down"]))
    return q, dataclasses.replace(cfg, experts_held=held, expert_offset=offset)


def _moe_plain(p, x, cfg):
    """Every token through every held expert, weighted by its routing
    weight there (zero where not routed): float64 numpy."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), p)
    x = np.asarray(x, np.float64).reshape(-1, cfg.d_model)
    s = 1.0 / (1.0 + np.exp(-(x @ p["router"])))
    pick = np.argsort(-(s + p["router_bias"]), axis=-1,
                      kind="stable")[:, :cfg.experts_per_token]
    w = np.take_along_axis(s, pick, -1)
    w = w / w.sum(-1, keepdims=True) * cfg.routed_scaling
    silu = lambda v: v / (1.0 + np.exp(-v))
    y = np.zeros_like(x)
    for j in range(cfg.experts_per_token):
        for t in range(x.shape[0]):
            e = pick[t, j] - cfg.expert_offset
            if 0 <= e < cfg.held_experts:
                h = silu(x[t] @ p["we_gate"][e]) * (x[t] @ p["we_up"][e])
                y[t] += w[t, j] * (h @ p["we_down"][e])
    return y


def _routed(p, x, cfg):
    with jax.default_matmul_precision("highest"):
        return moe_mod.routed_experts(p, x, cfg)


def _grouped_on(monkeypatch, backend):
    """The layer's grouped matmuls on ``backend`` (the Pallas kernels run
    in interpret mode on the CPU)."""
    monkeypatch.setattr(moe_mod, "grouped_matmul", functools.partial(
        ops.grouped_matmul, backend=backend))


@pytest.mark.parametrize("backend", ["pallas", "jnp"])
def test_shares_add_up_to_the_uncut_layer(monkeypatch, backend):
    """Eight disjoint shares of two experts each: their routed parts,
    summed, plus the shared experts counted once, equal the layer holding
    all sixteen; each share matches the plain formulation."""
    _grouped_on(monkeypatch, backend)
    cfg = MOE_CFG
    p = _layer_params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, cfg.d_model))
    shared = moe_mod.apply_mlp(p["shared"], x, "swiglu")
    with jax.default_matmul_precision("highest"):
        whole, _ = moe_mod.apply_dropless_moe(p, x, cfg)
    parts = []
    for o in range(0, cfg.num_experts, 2):
        ps, cs = _share(p, cfg, o, 2)
        part = _routed(ps, x.reshape(-1, cfg.d_model), cs)
        _close(part, _moe_plain(ps, x, cs), 1e-5)
        parts.append(part)
    total = sum(parts).reshape(x.shape) + shared
    _close(total, whole, 1e-5)


def test_no_token_is_dropped_when_every_token_picks_one_expert(monkeypatch):
    """A bias that sends every token to held expert 1: all 48 tokens reach
    it (a capacity layer would keep about k·N/E of them), and the layer's
    gradient matches the plain formulation's."""
    _grouped_on(monkeypatch, "pallas")
    cfg = MOE_CFG
    p = _layer_params(cfg, jax.random.PRNGKey(3))
    p["router_bias"] = p["router_bias"].at[1].set(10.0)
    ps, cs = _share(p, cfg, 0, 4)
    x = jax.random.normal(jax.random.PRNGKey(4), (48, cfg.d_model))
    _, idx = moe_mod.route(ps, x, cs)
    assert bool(jnp.all(jnp.any(idx == 1, axis=-1)))
    _close(_routed(ps, x, cs), _moe_plain(ps, x, cs), 1e-5)
    ct = jax.random.normal(jax.random.PRNGKey(5), x.shape)
    def grads(backend):
        _grouped_on(monkeypatch, backend)
        return jax.grad(lambda q: jnp.sum(_routed(q, x, cs) * ct))(ps)

    g = grads("pallas")
    g_plain = grads("jnp")
    for name in ("we_gate", "we_up", "we_down", "router"):
        _close(g[name], g_plain[name], 1e-5)
    assert float(jnp.abs(g["router_bias"]).max()) == 0.0
    # each held expert's gradient is nonzero: every token reached expert 1
    assert float(jnp.linalg.norm(g["we_down"][1])) > 0


def test_smoke_config_is_the_published_block():
    full = get_arch("moonlight_16b_a3b")
    assert (full.num_layers, full.d_model, full.num_experts,
            full.experts_per_token, full.vocab_size) == (27, 2048, 64, 6,
                                                         163840)
    assert full.attn_kind == "mla" and full.moe_layer == "dropless"
    assert full.head_dim == 128 + 64 and full.v_head_dim == 128
    smoke = get_arch("moonlight_16b_a3b", smoke=True)
    assert smoke.attn_kind == "mla" and smoke.held_experts < smoke.num_experts
