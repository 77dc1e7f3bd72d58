"""Plain reference of the ``moonlight_16b_a3b_ep8_d6`` cell's server update.

A DeepSeek-V3 decoder (arXiv:2412.19437 §2.1; the ``deepseek_v3`` model of
the Moonlight-16B-A3B config) at the widths of the configuration file, on
the share of one chip of an 8-chip expert-parallel group:

  * token embedding;
  * per layer, a pre-RMSNorm latent attention block: q = h·W_q split per
    head into q_nope (128) and q_pe (64); [c_kv, k_pe] = h·W_kva, c_kv
    RMS-normalized and up-projected to [k_nope, v] (128 + 128 per head);
    q_pe and k_pe rotated as DeepSeek-V3's code does (the pairs
    de-interleaved, then the rotate-half form), the single k_pe shared by
    every head; scores q_nope·k_nope + q_pe·k_pe over sqrt(192), causal;
    out = concat(P·v)·W_o;
  * then a pre-RMSNorm FFN: in layer 0 a SwiGLU of the dense width; in the
    expert layers the router (sigmoid scores of h·W_r in float32 over all
    64 experts, the top 6 by score + correction bias, their scores over
    their sum times 2.446) and, for each held expert, its SwiGLU applied to
    every token and weighted by that token's routing weight for it (zero
    where not routed there), plus the shared experts' SwiGLU;
  * a final RMSNorm, the LM head over the vocabulary slice, mean
    cross-entropy over the tokens whose label is not -1.

The share is the program's: the experts held are those of the parameters
given (0-7 of 64), the vocabulary is the slice, the shared experts are
computed once. The cut after the client's dense layer is quantized per
sequence (each sequence is one client's) and the cut gradient gains
λ·(z − z̃) (FedLite eq. 5); no downlink codec; Adam.

Departures from the published model, as the configuration's ``assumed``
states: the correction bias is held fixed (DeepSeek-V3 updates it every
step), no sequence-wise auxiliary loss is added (the config gives no weight
for it), and the optimizer is Adam where Moonlight was trained with Muon.
Layers, query blocks, experts and loss chunks are rematerialized so that
the float32 reference fits one chip. Imports nothing of the program.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench import params as P
from bench.reference import common as C

Q_BLOCK = 512
CE_CHUNK = 512


def _rms(scale, x, eps):
    x = x.astype(jnp.float32)
    y = x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return y * C.lift(scale, y)


def _rope(x, pos, theta):
    """DeepSeek-V3's rotation of q_pe / k_pe: the interleaved pairs are
    de-interleaved (evens, then odds) and rotated in the half-split form."""
    d = x.shape[-1]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]     # (S, d/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + rot * sin


def _attention(p, h, a, mode):
    B, S, _ = h.shape
    H, r, rope = a["num_heads"], a["kv_lora_rank"], a["qk_rope_dim"]
    nope, vd = a["head_dim"] - rope, a["v_head_dim"]
    f32 = jnp.float32
    q = C.mm(h, p["wq"], mode).astype(f32).reshape(B, S, H, nope + rope)
    kv_a = C.mm(h, p["wkv_a"], mode).astype(f32)
    c_kv = _rms(p["kv_norm"]["scale"], kv_a[..., :r], a["norm_eps"])
    kv = C.mm(c_kv, p["wkv_b"], mode).astype(f32).reshape(B, S, H,
                                                          nope + vd)
    pos = jnp.arange(S)
    q_nope = q[..., :nope]
    q_pe = _rope(q[..., nope:], pos, a["rope_theta"])
    k_pe = _rope(kv_a[..., r:].reshape(B, S, 1, rope), pos,
                 a["rope_theta"])[:, :, 0]                  # (B, S, rope)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    nb = max(S // Q_BLOCK, 1)
    bs = S // nb

    @jax.checkpoint
    def block(i):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, i * bs, bs, axis=1)
        qp = jax.lax.dynamic_slice_in_dim(q_pe, i * bs, bs, axis=1)
        s = C.einsum("bqhd,bshd->bhqs", qn, k_nope, mode) \
            + C.einsum("bqhd,bsd->bhqs", qp, k_pe, mode)
        s = s.astype(f32) / math.sqrt(nope + rope)
        qpos = i * bs + jnp.arange(bs)
        keep = qpos[:, None] >= pos[None, :]
        s = jnp.where(keep[None, None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        return C.einsum("bhqs,bshd->bqhd", pr, v, mode).astype(f32)

    # one block program looped over the query blocks, so that the compile
    # does not grow with the sequence
    out = jax.lax.map(block, jnp.arange(nb))          # (nb, B, bs, H, vd)
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, H * vd)
    return C.mm(out, p["wo"], mode).astype(f32)


def _swiglu(p, h, mode):
    f32 = jnp.float32
    g = C.mm(h, p["w_gate"], mode).astype(f32)
    u = C.mm(h, p["w_up"], mode).astype(f32)
    return C.mm(jax.nn.silu(g) * u, p["w_down"], mode).astype(f32)


def _routing(p, h, a, mode):
    """(N, E) weight of each expert for each token: zero where not
    routed there."""
    f32 = jnp.float32
    E, k = a["num_experts"], a["experts_per_token"]
    logits = C.mm(h, p["router"], mode).astype(f32)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + p["router_bias"].astype(f32)[None, :], k)
    picked = jnp.sum(jax.nn.one_hot(idx, E, dtype=f32), axis=1)   # (N, E)
    w = scores * picked
    return w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) \
        * a["routed_scaling"]


def _experts(p, h, a, mode):
    """Routed part of the held experts plus the shared experts."""
    B, S, D = h.shape
    hf = h.reshape(B * S, D)
    o, Eh = a["expert_offset"], a["experts_held"]
    weight = _routing(p, hf, a, mode)[:, o:o + Eh]                # (N, Eh)

    @jax.checkpoint
    def term(e):
        we = {"w_gate": p["we_gate"][e], "w_up": p["we_up"][e],
              "w_down": p["we_down"][e]}
        return weight[:, e][:, None] * _swiglu(we, hf, mode)

    y, _ = jax.lax.scan(lambda y, e: (y + term(e), None),
                        jnp.zeros((B * S, D), jnp.float32), jnp.arange(Eh))
    return y.reshape(B, S, D) + _swiglu(p["shared"], h, mode)


def _stack(layers, x, a, mode, moe):
    eps = a["norm_eps"]

    @jax.checkpoint
    def layer(x, lp):
        lp = lp["p0"]
        x = x + _attention(lp["mixer"], _rms(lp["ln1"]["scale"], x, eps), a,
                           mode)
        h = _rms(lp["ln2"]["scale"], x, eps)
        x = x + (_experts(lp["ffn"], h, a, mode) if moe
                 else _swiglu(lp["ffn"], h, mode))
        return x.astype(jnp.float32), None

    x, _ = jax.lax.scan(layer, x, layers)
    return x


def _client(cp, tokens, a, mode):
    x = jnp.take(cp["tok_embed"], tokens, axis=0).astype(jnp.float32)
    return _stack(cp["layers"], x, a, mode, moe=False)


def _server_loss(sp, z, labels, a, mode):
    x = _stack(sp["layers"], z, a, mode, moe=True)
    x = _rms(sp["final_norm"]["scale"], x, a["norm_eps"])
    B, S, D = x.shape
    nc = max(S // CE_CHUNK, 1)
    xc = x.reshape(B, nc, S // nc, D)
    lc = labels.reshape(B, nc, S // nc)

    @jax.checkpoint
    def chunk(xb, lb):
        logits = C.mm(xb, sp["head"], mode).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.maximum(lb, 0)[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - picked) * (lb >= 0))

    def body(total, i):
        return total + chunk(xc[:, i], lc[:, i]), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                            jnp.arange(nc))
    return total / jnp.maximum(jnp.sum(labels >= 0), 1)


@functools.partial(jax.jit, static_argnames=("static",),
                   donate_argnums=(0, 1, 2))
def _step(params, m, v, t, tokens, labels, *, static):
    arch, pq, lam, opt, mode = static
    a, pq, opt = dict(arch), dict(pq), dict(opt)
    B, S = tokens.shape
    z, client_vjp = jax.vjp(lambda cp: _client(cp, tokens, a, mode),
                            params["client"])
    zt, resid = C.quantize_clients(z.reshape(B * S, -1), S, pq)
    loss, (g_server, g_zt) = jax.value_and_grad(_server_loss, (0, 1))(
        params["server"], zt.reshape(z.shape), labels, a, mode)
    (g_client,) = client_vjp(g_zt + lam * resid.reshape(z.shape))
    grads = {"client": g_client, "server": g_server}
    norms = P.leaf_norms(grads)
    params, m, v = C.adam_step(params, grads, m, v, t, opt["lr"], opt["b1"],
                               opt["b2"], opt["eps"])
    return params, m, v, loss, norms


def run(cfg, mix, params0, batches, *, mode="highest", half_batch=False):
    """Train from ``params0`` (taken over: its buffers are donated) on
    ``batches``, one stacked cohort batch per step. Returns (losses, the
    first step's per-leaf gradient norms, final parameters).

    ``half_batch`` keeps only the first half of each cohort's sequences:
    the fault of a step that leaves half of the batch out."""
    if cfg["downlink"] != "none":
        raise ValueError("this reference has no downlink codec")
    static = (tuple(sorted(cfg["arch"].items())),
              tuple(sorted(cfg["pq"].items())), float(cfg["lam"]),
              tuple(sorted(cfg["optimizer"].items())), mode)
    params = params0
    m, v = C.adam_init(params)
    losses, first = [], None
    for t, b in enumerate(batches, start=1):
        tokens, labels = b["tokens"], b["labels"]
        if half_batch:
            keep = tokens.shape[0] // 2
            tokens, labels = tokens[:keep], labels[:keep]
        params, m, v, loss, norms = _step(
            params, m, v, jnp.float32(t), tokens, labels, static=static)
        if first is None:
            first = norms
        losses.append(loss)
    return losses, first, params
