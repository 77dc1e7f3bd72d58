"""Mixture-of-Experts layer with grouped, capacity-based scatter dispatch.

Dispatch is GShard/MaxText-style *grouped*: tokens are split into G groups
aligned with the batch sharding (G = pod·data shards when a mesh is
installed), and each group scatters into its own (E, C_g, D) buffer with
per-group capacity C_g = ceil(k·N_g/E · capacity_factor). This keeps the
position-cumsum and the scatter strictly local to a shard — without
grouping, XLA must treat the (E, C, D) scatter operand as replicated
("involuntary full rematerialization"), which costs hundreds of GiB/device
at 1M-token batches.

Expert parallelism: expert-stacked weights are sharded over "model" whenever
E divides the model axis (see sharding/rules.py); the grouped buffer carries
(batch-axes, "model") sharding so the token->expert all-to-all is inserted
by XLA from the constraints alone. When E does not divide (mixtral's 8
experts on a 16-wide axis), weights fall back to FSDP and the buffer shards
its capacity dim over "model" instead.

Overflow beyond capacity is dropped (Switch/GShard semantics, tested).

``apply_dropless_moe`` is DeepSeek-V3's layer (arXiv:2412.19437 §2.1.2) on
one expert-parallel share: the router scores all ``num_experts`` experts
(sigmoid), picks the top-k by score plus a fixed correction bias, and
weighs the picks by their scores renormalized and scaled; the layer holds
experts [expert_offset, expert_offset + experts_held) and computes their
part of the routed sum for every token routed to them, dropping none,
plus the shared experts, which every share computes alike. The token-slots
routed here are sorted by expert into a buffer sized for the worst case
(every slot held) and the held experts run as grouped matmuls over it
(``kernels.ops.grouped_matmul``); no exchange between shares is made.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels.ops import grouped_matmul
from repro.models.layers import apply_mlp, dense_init, mlp_init
from repro.sharding import axis_size, shard, shard_residual


def moe_init(key, cfg, dtype):
    ks = jax.random.split(key, 4)
    D, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    p = {"router": dense_init(ks[0], D, E, jnp.float32)}
    if cfg.mlp_type in ("swiglu", "geglu"):
        p["we_gate"] = _expert_init(ks[1], E, D, F, dtype)
    p["we_up"] = _expert_init(ks[2], E, D, F, dtype)
    p["we_down"] = _expert_init(ks[3], E, F, D, dtype)
    return p


def _expert_init(key, e, d_in, d_out, dtype):
    std = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (e, d_in, d_out), jnp.float32) * std).astype(dtype)


def _buffer_specs(num_experts: int):
    """(ebuf/out spec, hidden spec) for the grouped dispatch buffers.

    Expert-parallel: both sharded over experts. TP-in-expert fallback: the
    (G,E,C,D) buffers shard only over groups; the hidden (G,E,C,F) shards F
    over "model" to match the column-parallel expert weights (Megatron
    pattern), so w_down's row-parallel contraction reduce-scatters back."""
    if num_experts % max(axis_size("model"), 1) == 0:
        ep = (("pod", "data"), "model", None, None)
        return ep, ep
    return ((("pod", "data"), None, None, None),
            (("pod", "data"), None, None, "model"))


def _num_groups(batch: int) -> int:
    """Dispatch groups = batch shards (so each group is shard-local)."""
    shards = max(axis_size("pod"), 1) * max(axis_size("data"), 1)
    if shards > 1 and batch % shards == 0:
        return shards
    return 1


def apply_moe(p, x, cfg):
    """x: (B, S, D) -> (y, aux_loss). Grouped top-k routing with capacity."""
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    G = _num_groups(B)
    N = B * S
    Ng = N // G
    xg = x.reshape(G, Ng, D)

    logits = (xg.astype(jnp.float32) @ p["router"])            # (G, Ng, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_idx = jax.lax.top_k(probs, k)                 # (G, Ng, k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    # load-balance auxiliary loss (Switch-style), over ALL tokens
    me = jnp.mean(probs, axis=(0, 1))                          # (E,)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(gate_idx, E, dtype=jnp.float32),
                          axis=2), axis=(0, 1))
    aux = E * jnp.sum(me * ce) * cfg.router_aux_weight

    capacity = int(math.ceil(k * Ng / E * cfg.capacity_factor))
    capacity = max(8, -(-capacity // 8) * 8)                   # round up to 8

    def dispatch(xf, idx, w):
        """One group: (Ng, D), (Ng, k), (Ng, k) -> buffer + combine info.

        Scatters one expert-choice at a time (k <= 2 unrolled) — an
        (Ng·k, D) repeated-token buffer would double the live activation
        footprint per MoE layer."""
        flat_idx = idx.reshape(-1)                             # (Ng·k,)
        onehot = jax.nn.one_hot(flat_idx, E, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - onehot
        pos = jnp.sum(pos * onehot, axis=-1)
        keep = pos < capacity
        dest = jnp.where(keep, flat_idx * capacity + pos, E * capacity)
        dest2 = dest.reshape(-1, k)                            # (Ng, k)
        buf = jnp.zeros((E * capacity + 1, D), x.dtype)
        for j in range(k):
            buf = buf.at[dest2[:, j]].add(xf)
        return buf[:-1].reshape(E, capacity, D), dest2, keep.reshape(-1, k)

    buf_spec, hid_spec = _buffer_specs(E)
    ebuf, dest, keep = jax.vmap(dispatch)(xg, gate_idx, gate_w)
    ebuf = shard(ebuf, *buf_spec)                              # (G,E,C,D)

    if "we_gate" in p:
        act = jax.nn.silu if cfg.mlp_type == "swiglu" else (
            lambda v: jax.nn.gelu(v, approximate=True))
        h = act(jnp.einsum("gecd,edf->gecf", ebuf, p["we_gate"])) * \
            jnp.einsum("gecd,edf->gecf", ebuf, p["we_up"])
    else:
        h = jax.nn.gelu(jnp.einsum("gecd,edf->gecf", ebuf, p["we_up"]),
                        approximate=True)
    h = shard(h, *hid_spec)
    out_buf = jnp.einsum("gecf,efd->gecd", h, p["we_down"])
    out_buf = shard(out_buf, *buf_spec)

    def combine(flat_out, dest_g, w, keep_g):
        padded = jnp.concatenate(
            [flat_out.reshape(E * capacity, D), jnp.zeros((1, D), x.dtype)])
        y = jnp.zeros((Ng, D), x.dtype)
        for j in range(k):   # one gather per choice; no (Ng·k, D) buffer
            wj = (w[:, j] * keep_g[:, j]).astype(x.dtype)
            y = y + padded[dest_g[:, j]] * wj[:, None]
        return y

    y = jax.vmap(combine)(out_buf, dest, gate_w, keep)          # (G, Ng, D)
    y = y.reshape(B, S, D)
    return shard_residual(y), aux


# ---------------------------------------------------------------------------
# dropless layer over the held share of the experts (DeepSeek-V3)
# ---------------------------------------------------------------------------

def dropless_init(key, cfg, dtype):
    ks = jax.random.split(key, 5)
    D, F, E, Eh = cfg.d_model, cfg.expert_d_ff, cfg.num_experts, \
        cfg.held_experts
    p = {"router": dense_init(ks[0], D, E, jnp.float32),
         "router_bias": jnp.zeros((E,), jnp.float32),
         "we_gate": _expert_init(ks[1], Eh, D, F, dtype),
         "we_up": _expert_init(ks[2], Eh, D, F, dtype),
         "we_down": _expert_init(ks[3], Eh, F, D, dtype)}
    if cfg.num_shared_experts:
        p["shared"] = mlp_init(ks[4], D, F * cfg.num_shared_experts,
                               "swiglu", False, dtype)
    return p


def route(p, x, cfg):
    """x (N, D) -> (weights (N, k) f32, experts (N, k) int32): sigmoid
    scores in float32 over every expert, the top-k by score + correction
    bias (a fixed parameter: no gradient reaches it), weighted by their
    scores over the picks' sum, times ``routed_scaling``."""
    logits = jnp.matmul(x.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    bias = jax.lax.stop_gradient(p["router_bias"])
    _, idx = jax.lax.top_k(scores + bias[None, :], cfg.experts_per_token)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg.routed_scaling, idx


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _slots_by_expert(x, order, pos, k):
    """Row r of the sorted buffer is token order[r] // k: x (N, D) ->
    (N·k, D). The cotangent of each token sums its k slots, gathered by
    ``pos`` (slot -> buffer row), with no scatter."""
    return x[order // k]


def _slots_fwd(x, order, pos, k):
    return x[order // k], (order, pos)


def _slots_bwd(k, res, g):
    order, pos = res
    n = pos.shape[0] // k
    dx = jnp.sum(g[pos].reshape(n, k, -1).astype(jnp.float32), axis=1)
    return dx.astype(g.dtype), None, None


_slots_by_expert.defvjp(_slots_fwd, _slots_bwd)


@jax.custom_vjp
def _unsort(y, order, pos):
    """Buffer rows back to slot order: y (N·k, D) -> y[pos]; the cotangent
    is gathered back by ``order``."""
    return y[pos]


def _unsort_fwd(y, order, pos):
    return y[pos], (order, pos)


def _unsort_bwd(res, g):
    order, _ = res
    return g[order], None, None


_unsort.defvjp(_unsort_fwd, _unsort_bwd)


def routed_experts(p, x, cfg):
    """The held experts' part of the routed sum: x (N, D) -> (N, D).

    Every (token, pick) slot whose expert is held goes to the front of an
    (N·k, D) buffer, sorted by expert; the rows past the held slots are
    masked on the way in (their gradient) and the slots not held on the way
    out. The grouped matmuls' work follows the tokens routed here, and
    nothing is dropped whatever the load."""
    N, D = x.shape
    k, Eh = cfg.experts_per_token, cfg.held_experts
    with jax.named_scope("moe_route"):
        w, idx = route(p, x, cfg)
        local = idx - cfg.expert_offset
        held = (local >= 0) & (local < Eh)
        group = jnp.where(held, local, Eh).reshape(-1)        # (N·k,)
        onehot = jax.nn.one_hot(group, Eh + 1, dtype=jnp.int32)
        sizes = jnp.sum(onehot, axis=0)                       # (Eh+1,)
        starts = jnp.cumsum(sizes) - sizes
        rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot,
                       axis=-1)
        pos = starts[group] + rank        # slot -> buffer row (stable)
        order = jnp.argsort(group, stable=True)   # buffer row -> slot
        live = (jnp.arange(N * k) < jnp.sum(sizes[:Eh]))[:, None]
    with jax.named_scope("moe_experts"):
        gs = sizes[:Eh]
        xs = jnp.where(live, _slots_by_expert(x, order, pos, k), 0)
        h = jax.nn.silu(grouped_matmul(xs, p["we_gate"], gs)) \
            * grouped_matmul(xs, p["we_up"], gs)
        ys = grouped_matmul(h, p["we_down"], gs)
        # masked before it is weighted: a product's gradient would carry
        # the unwritten rows into the routing weights' gradient
        ys = jnp.where(held[..., None],
                       _unsort(ys, order, pos).reshape(N, k, D), 0)
        y = jnp.sum(ys.astype(jnp.float32) * w[..., None], axis=1)
    return y.astype(x.dtype)


def apply_dropless_moe(p, x, cfg):
    """x: (B, S, D) -> (y, aux = 0): the held experts' routed part plus the
    shared experts (no auxiliary loss)."""
    B, S, D = x.shape
    y = routed_experts(p, x.reshape(B * S, D), cfg).reshape(B, S, D)
    if "shared" in p:
        y = y + apply_mlp(p["shared"], x, "swiglu")
    return shard_residual(y), jnp.zeros((), jnp.float32)
