"""Plain reference of the ``femnist_cnn`` cell's server update.

The FEMNIST CNN of arXiv:2201.11865 Appendix C, split after the flatten
(§5): client conv 3x3x32 + ReLU, conv 3x3x64 + ReLU, 2x2 max-pool, flatten
(d = 9216, height-width-channel order); per-client grouped PQ of the cut;
server dense 128 + ReLU, dense 62; mean cross-entropy over the cohort. The
cut gradient goes back through the downlink codec per client and gains
λ·(z − z̃) (eq. 5); then SGD. Imports nothing of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import params as P
from bench.reference import common as C


def _conv(x, w, b, mode):
    dt = C.act_dtype(mode)
    prec = C.HIGHEST if mode == "highest" else None
    y = jax.lax.conv_general_dilated(
        x.astype(dt), w.astype(dt), (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec)
    return y + C.lift(b, y)


def client_forward(cp, images, mode):
    x = jax.nn.relu(_conv(images, cp["conv1_w"], cp["conv1_b"], mode))
    x = jax.nn.relu(_conv(x, cp["conv2_w"], cp["conv2_b"], mode))
    n, h, w, c = x.shape
    x = x.reshape(n, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))
    return x.reshape(n, -1)


def server_loss(sp, z, labels, mode):
    dt = C.act_dtype(mode)
    h = C.mm(z, sp["dense1_w"], mode).astype(dt)
    h = jax.nn.relu(h + C.lift(sp["dense1_b"], h))
    logits = C.mm(h, sp["dense2_w"], mode).astype(dt)
    logits = logits + C.lift(sp["dense2_b"], logits)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


@functools.partial(jax.jit, static_argnames=("rows", "cfgkey", "mode"))
def _grads(params, images, labels, *, rows, cfgkey, mode):
    pq, lam, downlink = dict(cfgkey[0]), cfgkey[1], cfgkey[2]
    z, client_vjp = jax.vjp(
        lambda cp: client_forward(cp, images, mode), params["client"])
    zt, resid = C.quantize_clients(z, rows, pq)
    loss, (g_server, g_zt) = jax.value_and_grad(server_loss, (0, 1))(
        params["server"], zt.astype(z.dtype), labels, mode)
    g_z = C.downlink_clients(g_zt, rows, downlink) \
        + lam * resid.astype(g_zt.dtype)
    (g_client,) = client_vjp(g_z.astype(z.dtype))
    return loss, {"client": g_client, "server": g_server}


def run(cfg, mix, params0, batches, *, mode="highest", half_batch=False):
    """Train from ``params0`` on ``batches`` (one stacked cohort batch per
    step). Returns (losses, the first step's per-leaf gradient norms, final
    parameters).

    ``half_batch`` keeps only the first half of each cohort's clients: the
    fault of a step that leaves half of the batch out."""
    rows = int(mix["client_batch"])
    cfgkey = (tuple(sorted(cfg["pq"].items())), float(cfg["lam"]),
              cfg["downlink"])
    lr = cfg["optimizer"]["lr"]
    params = jax.tree.map(lambda p: p.astype(C.act_dtype(mode)), params0)
    losses, first = [], None
    for b in batches:
        images, labels = b["image"], b["label"]
        if half_batch:
            keep = (images.shape[0] // rows // 2) * rows
            images, labels = images[:keep], labels[:keep]
        loss, grads = _grads(params, images, labels, rows=rows,
                             cfgkey=cfgkey, mode=mode)
        if first is None:
            first = P.leaf_norms(grads)
        params = C.sgd_step(params, grads, lr)
        losses.append(loss)
    return losses, first, params
