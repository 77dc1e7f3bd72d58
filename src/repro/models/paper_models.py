"""The paper's own three task models (Appendix C), split exactly as in §5.

  * FEMNIST CNN  — client: Conv(32,3x3) + Conv(64,3x3) + MaxPool + Flatten
                   (cut activation d = 12·12·64 = 9216, the paper's d);
                   server: Dense(128) + Dense(62).   client ≈ 1.6% of params.
  * SO Tag MLP   — client: one dense layer (bow 5000 -> 2000 = d);
                   server: one dense layer (2000 -> 1000 tags, multi-label).
  * SO NWP LSTM  — client: Embedding(vocab, 96) + LSTM + Dense (d = 96);
                   server: Dense(96 -> vocab).

Each model follows the same split API as TransformerLM (params =
{"client", "server"}; ``loss(params, batch, quantize=...)`` applies the
grouped PQ + gradient-corrected VJP at the cut), so ``make_train_step``
drives the paper models and the billion-parameter archs identically.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.compressors import (CutCompressor, CutState, PQCompressor,
                                    apply_cut)
from repro.core.quantizer import PQConfig
from repro.models.layers import row

Params = Dict[str, Any]


def _cut(model, x, lam, key, cut_state):
    """The cut codecs with the batch split into clients of
    ``model.client_batch`` examples, each clustered with its own codebooks;
    a batch that does not divide into clients (or client_batch=0) is one
    client. ``cut_state`` follows the caller's layout: EF memory shaped like
    ``x``, codebooks with a leading client axis under the split and without
    one for a single client; the new state comes back the same way."""
    b, cb = x.shape[0], model.client_batch
    c = b // cb if cb and b % cb == 0 else 1
    z = x.reshape(c, b // c, *x.shape[1:])
    if cut_state is not None:
        cut_state = _relayout(cut_state, z.shape,
                              (lambda a: a) if c > 1 else (lambda a: a[None]))
    up = None if model.pq is None else PQCompressor(model.pq)
    z, stats = apply_cut(z, up, model.downlink_compressor, lam, key=key,
                         state=cut_state)
    if "cut_state" in stats:
        stats["cut_state"] = _relayout(
            stats["cut_state"], x.shape,
            (lambda a: a) if c > 1 else (lambda a: a[0]))
    return z.reshape(x.shape), stats


def _relayout(state: CutState, ef_shape, lead) -> CutState:
    """``state`` with its EF memory reshaped to ``ef_shape`` and ``lead``
    applied to each codebook-state leaf."""
    return CutState(
        jax.tree.map(lead, state.quantizer),
        None if state.ef_memory is None
        else state.ef_memory.reshape(ef_shape))


def _split_loss(model, params: Params, batch, head, quantize: bool,
                lam_override, key, cut_state):
    """The split forward shared by the paper models: client half, the cut
    codecs, server half and ``head(logits, batch)``, each under the named
    scope that attributes its device operations (and their transposes) to
    one layer of the server update. Returns (loss, codec stats)."""
    with jax.named_scope("fl_client"):
        acts = model.client_forward(params["client"], batch)
    stats = {}
    if quantize:
        lam = model.lam if lam_override is None else lam_override
        with jax.named_scope("fl_uplink_codec"):
            acts, stats = _cut(model, acts, lam, key, cut_state)
    with jax.named_scope("fl_server"):
        loss = head(model.server_logits(params["server"], acts), batch)
    return loss, stats


# ---------------------------------------------------------------------------
# FEMNIST CNN
# ---------------------------------------------------------------------------

def _ce_head(logits, batch):
    labels = batch["label"]
    return -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(labels.shape[0]),
                                                labels])


@dataclasses.dataclass(frozen=True)
class FemnistCNN:
    """28x28x1 -> 62 classes; cut after flatten (d = 9216)."""
    num_classes: int = 62
    pq: Optional[PQConfig] = None
    lam: float = 0.0
    dropout: float = 0.0
    client_batch: int = 0   # examples per client for per-client PQ codebooks
    downlink_compressor: Optional[CutCompressor] = None

    cut_dim: int = 9216  # 12*12*64

    def init(self, key) -> Params:
        k1, k2, k3, k4 = jax.random.split(key, 4)
        he = lambda k, shp, fan: jax.random.normal(k, shp) * jnp.sqrt(2.0 / fan)
        return {
            "client": {
                "conv1_w": he(k1, (3, 3, 1, 32), 9), "conv1_b": jnp.zeros(32),
                "conv2_w": he(k2, (3, 3, 32, 64), 9 * 32), "conv2_b": jnp.zeros(64),
            },
            "server": {
                "dense1_w": he(k3, (9216, 128), 9216), "dense1_b": jnp.zeros(128),
                "dense2_w": he(k4, (128, self.num_classes), 128),
                "dense2_b": jnp.zeros(self.num_classes),
            },
        }

    def client_forward(self, cp: Params, batch) -> jax.Array:
        x = batch["image"]  # (B, 28, 28, 1)
        x = jax.lax.conv_general_dilated(
            x, cp["conv1_w"], (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + row(cp["conv1_b"], 4)
        x = jax.nn.relu(x)
        x = jax.lax.conv_general_dilated(
            x, cp["conv2_w"], (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + row(cp["conv2_b"], 4)
        x = jax.nn.relu(x)
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
        return x.reshape(x.shape[0], -1)  # (B, 9216)

    def server_logits(self, sp: Params, acts) -> jax.Array:
        h = jax.nn.relu(acts @ sp["dense1_w"] + row(sp["dense1_b"], 2))
        return h @ sp["dense2_w"] + row(sp["dense2_b"], 2)

    def loss(self, params: Params, batch, *, quantize: bool = True,
             lam_override=None, key=None, cut_state=None):
        ce, stats = _split_loss(self, params, batch, _ce_head, quantize,
                                lam_override, key, cut_state)
        return ce, dict(stats, ce=ce)

    def accuracy(self, params: Params, batch) -> jax.Array:
        acts = self.client_forward(params["client"], batch)
        logits = self.server_logits(params["server"], acts)
        return jnp.mean(jnp.argmax(logits, -1) == batch["label"])


# ---------------------------------------------------------------------------
# SO Tag MLP (multi-label)
# ---------------------------------------------------------------------------

def _bce_head(logits, batch):
    y = batch["tags"].astype(jnp.float32)  # (B, num_tags) multi-hot
    return jnp.mean(jnp.maximum(logits, 0) - logits * y +
                    jnp.log1p(jnp.exp(-jnp.abs(logits))))


@dataclasses.dataclass(frozen=True)
class SOTagMLP:
    bow_dim: int = 5000
    cut_dim: int = 2000
    num_tags: int = 1000
    pq: Optional[PQConfig] = None
    lam: float = 0.0
    client_batch: int = 0
    downlink_compressor: Optional[CutCompressor] = None

    def init(self, key) -> Params:
        k1, k2 = jax.random.split(key)
        glorot = lambda k, i, o: jax.random.normal(k, (i, o)) * jnp.sqrt(1.0 / i)
        return {
            "client": {"dense1_w": glorot(k1, self.bow_dim, self.cut_dim),
                       "dense1_b": jnp.zeros(self.cut_dim)},
            "server": {"dense2_w": glorot(k2, self.cut_dim, self.num_tags),
                       "dense2_b": jnp.zeros(self.num_tags)},
        }

    def client_forward(self, cp, batch):
        return jax.nn.relu(batch["bow"] @ cp["dense1_w"] + row(cp["dense1_b"], 2))

    def server_logits(self, sp, acts):
        return acts @ sp["dense2_w"] + row(sp["dense2_b"], acts.ndim)

    def loss(self, params, batch, *, quantize: bool = True,
             lam_override=None, key=None, cut_state=None):
        bce, stats = _split_loss(self, params, batch, _bce_head, quantize,
                                 lam_override, key, cut_state)
        return bce, dict(stats, bce=bce)

    def recall_at_5(self, params, batch):
        acts = self.client_forward(params["client"], batch)
        logits = self.server_logits(params["server"], acts)
        _, top5 = jax.lax.top_k(logits, 5)
        hits = jnp.take_along_axis(batch["tags"], top5, axis=-1).sum(-1)
        denom = jnp.minimum(batch["tags"].sum(-1), 5)
        return jnp.mean(hits / jnp.maximum(denom, 1))


# ---------------------------------------------------------------------------
# SO NWP LSTM
# ---------------------------------------------------------------------------

def _token_ce_head(logits, batch):
    labels = batch["labels"]  # (B, S), -1 = ignore
    mask = labels >= 0
    safe = jnp.maximum(labels, 0)
    lp = jax.nn.log_softmax(logits)
    ce = -jnp.sum(jnp.take_along_axis(lp, safe[..., None], -1)[..., 0] * mask)
    return ce / jnp.maximum(mask.sum(), 1)


@dataclasses.dataclass(frozen=True)
class SONwpLSTM:
    vocab: int = 10_000
    embed_dim: int = 96
    hidden: int = 670
    cut_dim: int = 96
    pq: Optional[PQConfig] = None
    lam: float = 0.0
    client_batch: int = 0
    downlink_compressor: Optional[CutCompressor] = None

    def init(self, key) -> Params:
        ks = jax.random.split(key, 5)
        g = lambda k, i, o: jax.random.normal(k, (i, o)) * jnp.sqrt(1.0 / i)
        return {
            "client": {
                "emb_w": jax.random.normal(ks[0], (self.vocab, self.embed_dim)) * 0.02,
                "lstm_wx": g(ks[1], self.embed_dim, 4 * self.hidden),
                "lstm_wh": g(ks[2], self.hidden, 4 * self.hidden),
                "lstm_b": jnp.zeros(4 * self.hidden),
                "dense1_w": g(ks[3], self.hidden, self.cut_dim),
                "dense1_b": jnp.zeros(self.cut_dim),
            },
            "server": {"dense2_w": g(ks[4], self.cut_dim, self.vocab),
                       "dense2_b": jnp.zeros(self.vocab)},
        }

    def client_forward(self, cp, batch):
        toks = batch["tokens"]  # (B, S)
        x = cp["emb_w"][toks]   # (B, S, E)
        B, S, _ = x.shape
        Hn = self.hidden

        def step(carry, xt):
            h, c = carry
            z = xt @ cp["lstm_wx"] + h @ cp["lstm_wh"] + row(cp["lstm_b"], 2)
            i, f, g_, o = jnp.split(z, 4, axis=-1)
            c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g_)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (h, c), h

        (h, c), hs = jax.lax.scan(step, (jnp.zeros((B, Hn)), jnp.zeros((B, Hn))),
                                  jnp.swapaxes(x, 0, 1))
        hs = jnp.swapaxes(hs, 0, 1)  # (B, S, H)
        return hs @ cp["dense1_w"] + row(cp["dense1_b"], 3)  # (B, S, 96)

    def server_logits(self, sp, acts):
        return acts @ sp["dense2_w"] + row(sp["dense2_b"], acts.ndim)

    def loss(self, params, batch, *, quantize: bool = True,
             lam_override=None, key=None, cut_state=None):
        ce, stats = _split_loss(self, params, batch, _token_ce_head,
                                quantize, lam_override, key, cut_state)
        return ce, dict(stats, ce=ce)

    def accuracy(self, params, batch):
        acts = self.client_forward(params["client"], batch)
        logits = self.server_logits(params["server"], acts)
        labels = batch["labels"]
        mask = labels >= 0
        ok = (jnp.argmax(logits, -1) == labels) * mask
        return ok.sum() / jnp.maximum(mask.sum(), 1)
