"""Plain reference of the ``starcoder2_3b_d6`` cell's server update.

A StarCoder2 decoder (arXiv:2402.19173) at the widths of the configuration
file: token embedding; per layer a pre-LayerNorm grouped-query attention
block (24 query heads, 2 key/value heads of 128, biases, rotary positions
with the rotate-half convention and theta from the file, causal with the
sliding window) and a pre-LayerNorm GELU (tanh) MLP with biases, each added
to the residual stream; after the server's layers a final LayerNorm and an
untied LM head; mean cross-entropy over the tokens whose label is not -1.
The cut after the client's layers is quantized per sequence (each sequence
is one client's) and the cut gradient gains λ·(z − z̃) (FedLite eq. 5); no
downlink codec; Adam. Each layer, query block and loss chunk is
rematerialized so that the float32 reference fits one chip. Imports nothing
of the program.
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


def _norm(p, x, eps):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + eps)
    return y * C.lift(p["scale"], y) + C.lift(p["bias"], y)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs[None, :]      # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(p, h, a, mode):
    B, S, _ = h.shape
    H, Kv, hd = a["num_heads"], a["num_kv_heads"], a["head_dim"]
    G = H // Kv
    f32 = jnp.float32
    q = C.mm(h, p["wq"], mode).astype(f32)
    k = C.mm(h, p["wk"], mode).astype(f32)
    v = C.mm(h, p["wv"], mode).astype(f32)
    q, k, v = (q + C.lift(p["wq_b"], q), k + C.lift(p["wk_b"], k),
               v + C.lift(p["wv_b"], v))
    pos = jnp.arange(S)
    q = _rope(q.reshape(B, S, H, hd), pos, a["rope_theta"])
    k = _rope(k.reshape(B, S, Kv, hd), pos, a["rope_theta"])
    v = v.reshape(B, S, Kv, hd)
    window = a["sliding_window"] or S + 1
    nb = max(S // Q_BLOCK, 1)
    qb = q.reshape(B, nb, S // nb, Kv, G, hd)

    @jax.checkpoint
    def block(i):
        qi = qb[:, i]
        s = C.einsum("bqkgh,bskh->bkgqs", qi, k, mode) / math.sqrt(hd)
        qpos = i * (S // nb) + jnp.arange(S // nb)
        keep = (qpos[:, None] >= pos[None, :]) \
            & (qpos[:, None] - pos[None, :] < window)
        s = jnp.where(keep[None, None, None], s.astype(f32), -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        return C.einsum("bkgqs,bskh->bqkgh", pr, v, mode).astype(f32)

    out = jnp.concatenate([block(i) for i in range(nb)], axis=1)
    y = C.mm(out.reshape(B, S, H * hd), p["wo"], mode).astype(f32)
    return y + C.lift(p["wo_b"], y)


def _mlp(p, h, mode):
    f32 = jnp.float32
    u = C.mm(h, p["w_up"], mode).astype(f32)
    u = jax.nn.gelu(u + C.lift(p["w_up_b"], u), approximate=True)
    y = C.mm(u, p["w_down"], mode).astype(f32)
    return y + C.lift(p["w_down_b"], y)


def _stack(layers, x, a, mode):
    eps = a["norm_eps"]

    @jax.checkpoint
    def layer(x, lp):
        lp = lp["p0"]
        x = x + _attention(lp["mixer"], _norm(lp["ln1"], x, eps), a, mode)
        x = x + _mlp(lp["ffn"], _norm(lp["ln2"], x, eps), mode)
        return x.astype(jnp.float32), None

    x, _ = jax.lax.scan(layer, x, layers)
    return x


def _client(cp, tokens, a, mode):
    x = jnp.take(cp["tok_embed"], tokens, axis=0).astype(jnp.float32)
    return _stack(cp["layers"], x, a, mode)


def _server_loss(sp, z, labels, a, mode):
    x = _stack(sp["layers"], z, a, mode)
    x = _norm(sp["final_norm"], x, a["norm_eps"])
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

    total = sum(chunk(xc[:, i], lc[:, i]) for i in range(nc))
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
