"""The benchmark's one traffic generator and the pooled client dataset.

A traffic mix is a JSON file of parameters (``bench/traffic/<name>.json``).
``make_batches`` reads it and makes, on the device and in one jitted call from
the seed, ``batches_per_client`` batches for every client of the population:

  * ``"kind": "images"`` — FEMNIST-like 28x28x1 images: smoothed class
    prototypes plus Gaussian noise, labels drawn from a per-client
    Dirichlet(alpha) class mixture (the non-IID partition of Kairouz et al.
    2019 §3.1). A copy of the repository's image generator, so that a change
    to the program cannot move the yardstick.
  * ``"kind": "tokens"`` — per-topic unigram tables and a topic-dependent
    bigram shift: token t is ``(token[t-1] + shift[topic]) % V`` with
    probability ``markov_p``, else a fresh unigram draw. Vectorized: a token
    is the last fresh draw at or before it, shifted once per step since.
    Labels are the next token, -1 at the end of each row.

``Pool`` hands the trainer a pooled batch per (client, call): call ``j`` of
client ``c`` gets slot ``j % batches_per_client``. It does no device work
and never reads a key back to the host, and it logs every request so that
the reference is fed exactly the batches the timed path was fed.
"""

from __future__ import annotations

import functools
import json
from pathlib import Path
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

KINDS = ("images", "tokens")


def load(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        mix = json.load(f)
    if mix.get("kind") not in KINDS:
        raise ValueError(f"{path}: kind must be one of {KINDS}")
    return mix


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    if seed < 0:
        raise ValueError(f"seed {seed} must be >= 0")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


# ---------------------------------------------------------------------------
# images
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("clients", "per_client", "batch",
                                             "num_classes"))
def _images(key, alpha, noise, *, clients: int, per_client: int, batch: int,
            num_classes: int):
    kp, km, kl, kn = jax.random.split(key, 4)
    protos = jax.random.normal(kp, (num_classes, 28, 28))
    # 3x3 box smoothing with edge padding, as the repository's generator
    padded = jnp.pad(protos, ((0, 0), (1, 1), (1, 1)), mode="edge")
    protos = sum(padded[:, i:i + 28, j:j + 28]
                 for i in range(3) for j in range(3)) / 9.0
    mix = jax.random.dirichlet(km, alpha * jnp.ones(num_classes), (clients,))
    logits = jnp.log(mix + 1e-9)[:, None, None, :]
    labels = jax.random.categorical(kl, logits,
                                    shape=(clients, per_client, batch))
    imgs = protos[labels] + noise * jax.random.normal(
        kn, (clients, per_client, batch, 28, 28))
    return {"image": imgs[..., None], "label": labels.astype(jnp.int32)}


# ---------------------------------------------------------------------------
# tokens
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("clients", "per_client", "batch",
                                             "seq", "vocab", "num_topics"))
def _tokens(key, alpha, markov_p, *, clients: int, per_client: int,
            batch: int, seq: int, vocab: int, num_topics: int):
    kt, ks, km, kr, ku, kb = jax.random.split(key, 6)
    topic_logits = 2.0 * jax.random.normal(kt, (num_topics, vocab))
    cdf = jnp.cumsum(jax.nn.softmax(topic_logits, axis=-1), axis=-1)
    shifts = jax.random.randint(ks, (num_topics,), 1, vocab - 1)
    mix = jax.random.dirichlet(km, alpha * jnp.ones(num_topics), (clients,))
    rows = (clients, per_client, batch)
    topic = jax.random.categorical(kr, jnp.log(mix + 1e-9)[:, None, None, :],
                                   shape=rows)
    # inverse-CDF unigram draws: O(log V) per token, not a V-wide Gumbel
    u = jax.random.uniform(ku, rows + (seq,))
    fresh = jax.vmap(jnp.searchsorted)(
        cdf[topic].reshape(-1, vocab), u.reshape(-1, seq)).reshape(u.shape)
    fresh = jnp.minimum(fresh, vocab - 1)
    markov = jax.random.uniform(kb, rows + (seq,)) < markov_p
    markov = markov.at[..., 0].set(False)
    pos = jnp.broadcast_to(jnp.arange(seq), markov.shape)
    last = jax.lax.cummax(jnp.where(markov, 0, pos), axis=markov.ndim - 1)
    steps = (pos - last).astype(jnp.int32)
    src = jnp.take_along_axis(fresh, last, axis=-1)
    toks = (src + steps * shifts[topic][..., None]) % vocab
    labels = jnp.concatenate(
        [toks[..., 1:], jnp.full(rows + (1,), -1, toks.dtype)], axis=-1)
    return {"tokens": toks.astype(jnp.int32), "labels": labels.astype(jnp.int32)}


def make_batches(mix: Dict[str, Any], seed: int, *, vocab: int = 0):
    """Every client's pooled batches: leaves shaped (clients, slots, batch,
    ...). One jitted call from the seed."""
    key = jax.random.fold_in(seed_key(seed), 0x7AFF1C)
    dims = dict(clients=int(mix["num_clients"]),
                per_client=int(mix["batches_per_client"]),
                batch=int(mix["client_batch"]))
    if mix["kind"] == "images":
        return _images(key, float(mix["alpha"]), float(mix["noise"]),
                       num_classes=int(mix["num_classes"]), **dims)
    if vocab <= 1:
        raise ValueError("token traffic needs the configuration's vocab")
    return _tokens(key, float(mix["alpha"]), float(mix["markov_p"]),
                   seq=int(mix["seq"]), vocab=int(vocab),
                   num_topics=int(mix["num_topics"]), **dims)


@jax.jit
def _unstack(batches):
    c, s = jax.tree.leaves(batches)[0].shape[:2]
    return [jax.tree.map(lambda x: x[i, j], batches)
            for i in range(c) for j in range(s)]


class Pool:
    """A `FederatedDataset` stand-in over pooled device batches.

    ``sample_batch(cid, key, batch)`` returns the client's next pooled batch
    (a pure host lookup); ``requests`` logs ``(cid, slot)`` per call."""

    def __init__(self, batches: Dict[str, jax.Array], *, annotate=None):
        leaves = jax.tree.leaves(batches)
        self.num_clients, self.slots, self.batch = leaves[0].shape[:3]
        self.client_weights = np.full(self.num_clients, 1.0 / self.num_clients)
        self.batches = batches
        # one device array per (client, slot), sliced once at set-up by one
        # program (eager slices with constant indices compile one each)
        flat = _unstack(batches)
        self._parts = [flat[c * self.slots:(c + 1) * self.slots]
                       for c in range(self.num_clients)]
        self._calls = [0] * self.num_clients
        self.requests: List[Tuple[int, int]] = []
        self._annotate = annotate

    def sample_batch(self, client_id: int, key=None, batch: int = 0, **_):
        del key
        if batch and batch != self.batch:
            raise ValueError(f"pool rows per client are {self.batch}, "
                             f"the trainer asked for {batch}")
        cid = int(client_id)
        slot = self._calls[cid] % self.slots
        self._calls[cid] += 1
        self.requests.append((cid, slot))
        if self._annotate is None:
            return self._parts[cid][slot]
        with self._annotate("fetch"):
            return self._parts[cid][slot]

    def cohort_batch(self, requests):
        """The stacked batch that ``requests`` (in participant order) made."""
        cs = jnp.asarray([c for c, _ in requests])
        ss = jnp.asarray([s for _, s in requests])
        return jax.tree.map(
            lambda x: x[cs, ss].reshape((-1,) + x.shape[3:]), self.batches)
