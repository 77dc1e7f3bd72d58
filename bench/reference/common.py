"""Plain pieces the references share: arithmetic at a stated precision, the
grouped product quantizer of FedLite (arXiv:2201.11865 §4.1) with its
λ-corrected cut gradient (§4.2, eq. 5), the top-k + b-bit scalar downlink
codec, and the optimizers. Written from the paper and the configuration
files; imports nothing of the program.

Precisions (``mode``):
  * ``"highest"`` — float32 arrays, matmuls and convolutions at
    Precision.HIGHEST: the reference.
  * ``"bfloat16"`` — every array and product in bfloat16: the control of a
    float32 configuration.
  * ``"float8"`` — matmul operands rounded to float8_e4m3fn, products
    accumulated in float32: the control of a bfloat16 configuration.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("highest", "bfloat16", "float8")


def act_dtype(mode):
    return jnp.bfloat16 if mode == "bfloat16" else jnp.float32


def lift(v, like):
    """A (d,) vector lifted to the rank of ``like`` (explicit: implicit rank
    promotion is an error under the repository's tests), in float32 unless
    the mode's dtype is given by ``like``."""
    return v.astype(like.dtype).reshape((1,) * (like.ndim - 1) + v.shape)


def mm(a, b, mode):
    """a @ b at the mode's precision."""
    if mode == "highest":
        return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                          precision=HIGHEST)
    if mode == "bfloat16":
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))
    if mode == "float8":
        f8 = jnp.float8_e4m3fn
        return jnp.matmul(a.astype(f8), b.astype(f8),
                          preferred_element_type=jnp.float32)
    raise ValueError(f"mode {mode!r} not one of {MODES}")


def einsum(spec, a, b, mode):
    if mode == "highest":
        return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                          precision=HIGHEST)
    if mode == "bfloat16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))
    f8 = jnp.float8_e4m3fn
    return jnp.einsum(spec, a.astype(f8), b.astype(f8),
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# grouped product quantizer, one client at a time
# ---------------------------------------------------------------------------

def pq_client(z, q, clusters, iters):
    """Quantize one client's cut activations z (n, d) with q subvectors per
    row, one codebook of ``clusters`` centroids over all subvector positions
    (R = 1), seeded by farthest-point selection on a strided subsample of
    max(4L, 256) subvectors, then ``iters`` Lloyd iterations (an empty
    cluster keeps its centroid). Subvectors are taken position-major:
    all rows' subvector 0, then all rows' subvector 1, ...

    The quantizer is stated at float32 and Precision.HIGHEST in every
    configuration, so it runs so in the controls too.

    Returns (z̃, z − z̃), both (n, d) float32."""
    n, d = z.shape
    ds = d // q
    dt = jnp.float32
    x = z.astype(dt).reshape(n, q, ds).transpose(1, 0, 2).reshape(q * n, ds)
    m = x.shape[0]
    sub = min(m, max(4 * clusters, 256))
    xs = x[::max(m // sub, 1)][:sub]
    cents = jnp.zeros((clusters, ds), dt).at[0].set(xs[0])
    mind = jnp.sum(jnp.square(xs - xs[0][None, :]), axis=-1)
    for c in range(1, clusters):
        pick = xs[jnp.argmax(mind)]
        cents = cents.at[c].set(pick)
        mind = jnp.minimum(mind, jnp.sum(jnp.square(xs - pick[None, :]), -1))

    def nearest(cents):
        # argmin ‖x − c‖² = argmax 2·x·c − ‖c‖² (the ‖x‖² term is common)
        score = 2.0 * mm(x, cents.T, "highest") \
            - jnp.sum(cents * cents, axis=-1)[None, :]
        return jnp.argmax(score, axis=-1)

    for _ in range(iters):
        code = nearest(cents)
        onehot = jax.nn.one_hot(code, clusters, dtype=dt)
        counts = jnp.sum(onehot, axis=0)
        dev = mm(onehot.T, x - cents[code], "highest")
        step = dev / jnp.maximum(counts, 1)[:, None]
        cents = cents + jnp.where(counts[:, None] > 0, step, 0)
    zt = cents[nearest(cents)]
    back = lambda g: g.reshape(q, n, ds).transpose(1, 0, 2).reshape(n, d)
    return back(zt), back(x - zt)


def quantize_clients(z, rows_per_client, pq):
    """Per-client PQ over a (clients·rows, d) cut: (z̃, residual)."""
    d = z.shape[-1]
    zc = z.reshape(-1, rows_per_client, d)
    zt, res = jax.vmap(lambda zi: pq_client(
        zi, pq["num_subvectors"], pq["num_clusters"], pq["kmeans_iters"]))(zc)
    return zt.reshape(z.shape), res.reshape(z.shape)


# ---------------------------------------------------------------------------
# downlink codec: top-k by magnitude, then b-bit uniform scalar quantization
# of the surviving values over their [min, max] range, nearest rounding
# ---------------------------------------------------------------------------

def parse_downlink(spec):
    """'none' or 'chain:topk(k=..)+scalarq(bits=..)' -> (k, bits)."""
    if spec == "none":
        return None
    m = re.fullmatch(r"chain:topk\(k=([0-9.]+)\)\+scalarq\(bits=(\d+)\)",
                     spec)
    if not m:
        raise ValueError(f"downlink {spec!r} has no reference")
    return float(m.group(1)), int(m.group(2))


def downlink_client(g, k_frac, bits):
    flat = g.reshape(-1).astype(jnp.float32)
    k = max(int(round(k_frac * flat.shape[0])), 1)
    _, idx = jax.lax.top_k(jnp.abs(flat), k)
    vals = flat[idx]
    lo, hi = jnp.min(vals), jnp.max(vals)
    levels = (1 << bits) - 1
    scale = (hi - lo) / levels
    scale = jnp.where(scale > 0, scale, 1.0)
    codes = jnp.clip(jnp.round((vals - lo) / scale), 0, levels)
    out = jnp.zeros_like(flat).at[idx].set(lo + codes * scale)
    return out.reshape(g.shape).astype(g.dtype)


def downlink_clients(g, rows_per_client, spec):
    kb = parse_downlink(spec)
    if kb is None:
        return g
    gc = g.reshape((-1, rows_per_client) + g.shape[1:])
    out = jax.vmap(lambda gi: downlink_client(gi, *kb))(gc)
    return out.reshape(g.shape)


# ---------------------------------------------------------------------------
# optimizers (updates computed in float32, stored in the parameter's type)
# ---------------------------------------------------------------------------

def sgd_step(params, grads, lr):
    return jax.tree.map(
        lambda p, g: (p.astype(jnp.float32) - lr * g.astype(jnp.float32))
        .astype(p.dtype), params, grads)


def adam_init(params):
    z = lambda p: jnp.zeros(p.shape, jnp.float32)
    return jax.tree.map(z, params), jax.tree.map(z, params)


def adam_step(params, grads, m, v, t, lr, b1=0.9, b2=0.999, eps=1e-8):
    """Adam (Kingma & Ba 2015) at step t (1-based). The update is rounded
    to the parameter's type before it is added, as a stored-in-bf16
    parameter receives it."""
    g32 = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, g32)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, g32)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, a, b: p + (-lr * (a / bc1) / (jnp.sqrt(b / bc2) + eps))
        .astype(p.dtype), params, m, v)
    return params, m, v
