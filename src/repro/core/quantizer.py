"""FedLite's grouped product quantizer (paper §4.1).

Given a batch of activation vectors Z ∈ R^{N×d}:

  (i)   divide each vector into ``q`` subvectors of size d/q;
  (ii)  stack subvectors into ``R`` groups by subvector index — group ``r``
        holds subvector positions [r·q/R, (r+1)·q/R) of every example, so all
        positions in a group share one codebook;
  (iii) K-means with ``L`` centroids per group; each subvector is represented
        by the index of its nearest centroid.

Uplink message = codebooks (φ·(d/q)·L·R bits) + codes (N·q·⌈log2 L⌉ bits),
vs. φ·d·N uncompressed — the paper's φdRL/q + Bq·log2 L with N playing B.

Special cases recovered exactly:
  * q = 1             → vanilla K-means on whole vectors
  * R = q  (q > 1)    → vanilla product quantization (codebook per position)
  * R = 1  (default)  → the paper's best trade-off: one shared codebook

Where this sits in the compressor stack
---------------------------------------
This module is the PQ *math*; it is one codec among several. The
direction-agnostic registry in ``core/compressors.py`` wraps it as the
``"pq"`` `CutCompressor` (the uplink default — what ``apply_cut`` runs at
every model's cut), next to ``none``,
``topk``, ``scalarq`` and ``chain`` (the downlink gradient codecs). Analytic
bits come from ``PQConfig.message_bits`` here (the paper's §4.1 cost model,
φdRL/q + Bq·log2 L); *measured* bits come from the tagged wire codec in
``federated/wire.py``, which serializes the `QuantizedBatch` produced here
as a ``pq`` payload (fp16 codebooks + ceil(log2 L)-bit packed codes) and
must agree with the analytic count to within the 24 B header.

Cross-round codebook warm-start
-------------------------------
FedLite's stateless-client story rebuilds codebooks from scratch every
round; in the simulation (and in any deployment where a client persists a
few KB between rounds) the previous round's codebook is an excellent
initializer, because activation distributions drift slowly. `QuantizerState`
carries the per-group fp32 codebooks plus a round counter across rounds:

  * cold round (``state is None`` / ``quantize``'s default): FPS/kmeans++
    seeding + ``kmeans_iters`` Lloyd iterations — the paper's behavior.
  * warm round (``quantize_stateful`` with a prior state): Lloyd resumes
    from ``state.codebooks`` and runs only ``PQConfig.warm_iters``
    iterations (default ``kmeans_iters // 2``), roughly halving the
    steady-state per-step K-means cost.

The state is threaded by the callers that own round boundaries —
``core/compressors.PQCompressor.compress_stateful`` inside the train step
and ``federated/runtime.FederatedTrainer`` across scheduler rounds — and it
is also what the ``pq-delta`` wire kind (``federated/wire.py``) diffs
against to shrink the codebook component of the uplink message.

Selecting a quantizer backend
-----------------------------
``PQConfig.backend`` picks the compute backend for the Lloyd iterations
(assign + the fused deviation-accumulate update, ``repro.kernels.
lloyd_update``) and the final encode (assignment + dequantize + residual):

  * ``"auto"`` (default) — the fused Pallas kernel (compiled Mosaic) on TPU,
    pure-jnp elsewhere. This is what production configs should use.
  * ``"jnp"``  — pure-jnp everywhere; the reference/CPU path.
  * ``"pallas"`` — force the Pallas kernels; off-TPU they run in interpret
    mode, which is for parity validation, not speed.

The final encode is *fused*: one pass produces the dequantized activations
z̃, the residual z − z̃ (consumed by the gradient-corrected VJP of
``core/compressors.uplink`` — it is NOT recomputed there), and the integer
codes. On TPU this is one HBM
read + two writes per element instead of the three sweeps (assign, gather,
subtract) of the naive path. Backends live in a registry
(``repro.core.kmeans.register_backend``) so new substrates can be added
without touching this module; the scalarq compressor's quantize/pack
kernels (``repro.kernels.scalar_quant``) ride the same registry resolution.
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import kmeans as _km


def bits_per_code(num_clusters: int) -> int:
    """Packed index width b = ceil(log2 L); a single cluster needs no codes.

    The one formula both the analytic accounting (`PQConfig`) and the wire
    codec (`federated/wire.py`) use."""
    return 0 if num_clusters <= 1 else \
        max(math.ceil(math.log2(num_clusters)), 1)


@dataclasses.dataclass(frozen=True)
class PQConfig:
    """Static quantizer hyperparameters (hashable: usable as a jit static)."""
    num_subvectors: int          # q — subvectors per activation vector
    num_clusters: int            # L — centroids per group
    num_groups: int = 1          # R — codebook groups (R=1 is the paper default)
    kmeans_iters: int = 8
    phi_bits: int = 64           # float width used for *accounting* (paper: 64)
    kmeans_chunk: int = 4096
    backend: str = "auto"        # "jnp" | "pallas" | "auto" (see module doc)
    warm_iters: Optional[int] = None  # Lloyd iters on warm rounds
    #                                   (None = kmeans_iters // 2)

    def __post_init__(self):
        if self.num_subvectors % self.num_groups != 0:
            raise ValueError(
                f"q={self.num_subvectors} must be divisible by R={self.num_groups}")
        if self.num_clusters < 1:
            raise ValueError("L must be >= 1")
        if self.backend not in _km.available_backends():
            raise ValueError(
                f"backend={self.backend!r} not one of {_km.available_backends()}")
        if self.warm_iters is not None and self.warm_iters < 0:
            raise ValueError(f"warm_iters={self.warm_iters} must be >= 0")

    @property
    def q(self) -> int:
        return self.num_subvectors

    @property
    def r(self) -> int:
        return self.num_groups

    @property
    def l(self) -> int:
        return self.num_clusters

    @property
    def effective_warm_iters(self) -> int:
        """Lloyd iterations on a warm-started round (see module docstring)."""
        return self.kmeans_iters // 2 if self.warm_iters is None \
            else self.warm_iters

    def subvector_dim(self, d: int) -> int:
        if d % self.num_subvectors != 0:
            raise ValueError(f"d={d} not divisible by q={self.num_subvectors}")
        return d // self.num_subvectors

    # ---- wire-layout metadata (consumed by federated/wire.py) ----------
    @property
    def bits_per_code(self) -> int:
        """Packed index width b = ceil(log2 L); L=1 transmits no codes."""
        return bits_per_code(self.num_clusters)

    def codebook_shape(self, d: int) -> tuple:
        """(R, L, d/q) — the centroid tensor the uplink carries."""
        return (self.num_groups, self.num_clusters, self.subvector_dim(d))

    def num_codes(self, n: int) -> int:
        """Total cluster indices for n activation vectors (= R·(q/R)·n)."""
        return n * self.num_subvectors

    # ---- communication accounting (paper §4.1) -------------------------
    def codebook_bits(self, d: int, phi_bits: Optional[int] = None) -> int:
        # R groups × L centroids × (d/q) dims × φ bits  ==  φ·d·R·L/q
        phi = self.phi_bits if phi_bits is None else phi_bits
        return phi * self.subvector_dim(d) * self.num_clusters * self.num_groups

    def codes_bits(self, n: int) -> int:
        return self.num_codes(n) * self.bits_per_code

    def message_bits(self, n: int, d: int, phi_bits: Optional[int] = None) -> int:
        return self.codebook_bits(d, phi_bits) + self.codes_bits(n)

    def uncompressed_bits(self, n: int, d: int,
                          phi_bits: Optional[int] = None) -> int:
        phi = self.phi_bits if phi_bits is None else phi_bits
        return phi * d * n

    def compression_ratio(self, n: int, d: int,
                          phi_bits: Optional[int] = None) -> float:
        return self.uncompressed_bits(n, d, phi_bits) / \
            max(self.message_bits(n, d, phi_bits), 1)


class QuantizedBatch(NamedTuple):
    dequantized: jax.Array   # (N, d) — z̃, same dtype as input
    codes: jax.Array         # (R, q/R·N) int32 cluster assignments
    codebooks: jax.Array     # (R, L, d/q)
    distortion: jax.Array    # () mean ‖z − z̃‖² per vector
    residual: jax.Array      # z − z̃, input shape + dtype (fused with encode;
                             # distortion is accumulated in fp32 before the cast)


class QuantizerState(NamedTuple):
    """Cross-round quantizer carry: the per-group codebooks of the last
    round (kept in fp32 — the Lloyd compute dtype) and a round counter.

    An all-array NamedTuple: jit/vmap-transparent, so trainers thread it
    through jitted steps and stack it per client. ``rounds`` counts how many
    quantizes contributed to ``codebooks`` (0-based warm lineage length)."""
    codebooks: jax.Array     # (R, L, d/q) fp32
    rounds: jax.Array        # () int32


def init_quantizer_state(qb: QuantizedBatch) -> QuantizerState:
    """Bootstrap a warm-start state from a cold round's output."""
    return QuantizerState(codebooks=qb.codebooks.astype(jnp.float32),
                          rounds=jnp.ones((), jnp.int32))


def _to_groups(z: jax.Array, cfg: PQConfig) -> jax.Array:
    """(N, d) -> (R, (q/R)·N, d/q) grouping consecutive subvector positions."""
    n, d = z.shape
    dsub = cfg.subvector_dim(d)
    # (N, q, dsub) -> (q, N, dsub): group r = positions [r·q/R, (r+1)·q/R)
    sub = z.reshape(n, cfg.q, dsub).transpose(1, 0, 2)
    return sub.reshape(cfg.r, (cfg.q // cfg.r) * n, dsub)


def _from_groups(groups: jax.Array, n: int, d: int, cfg: PQConfig) -> jax.Array:
    dsub = cfg.subvector_dim(d)
    sub = groups.reshape(cfg.q, n, dsub).transpose(1, 0, 2)
    return sub.reshape(n, d)


def quantize(z: jax.Array, cfg: PQConfig,
             key: Optional[jax.Array] = None, *,
             state: Optional[QuantizerState] = None) -> QuantizedBatch:
    """Quantize a batch of activation vectors with the grouped PQ scheme.

    ``z`` may have any leading shape; it is flattened to (N, d) where d is the
    trailing dim. The returned ``dequantized`` has the original shape.

    K-means (Lloyd) runs exactly once; the final dequantize + residual step is
    the backend's fused encode (``repro.kernels.pq_quantize`` under the
    Pallas backend), so callers that need the residual — the gradient
    correction — get it for free instead of re-deriving it from z̃.

    ``state`` (a previous round's `QuantizerState`) switches Lloyd to the
    warm-start path: seeding is skipped and only ``cfg.effective_warm_iters``
    iterations run from ``state.codebooks``. Callers that carry state across
    rounds should use ``quantize_stateful``, which also returns the updated
    state.
    """
    orig_shape = z.shape
    d = orig_shape[-1]
    z2 = z.reshape(-1, d)
    n = z2.shape[0]

    groups = _to_groups(z2.astype(jnp.float32), cfg)  # (R, M, dsub)
    if state is None:
        cents = _km.batched_lloyd(
            groups, cfg.num_clusters, cfg.kmeans_iters, key=key,
            chunk=cfg.kmeans_chunk, backend=cfg.backend)
    else:
        cents = _km.batched_lloyd(
            groups, cfg.num_clusters, cfg.effective_warm_iters, key=None,
            chunk=cfg.kmeans_chunk, backend=cfg.backend,
            init_centroids=state.codebooks.astype(jnp.float32))
    # fused final pass per group: z̃ + residual + codes in one sweep
    enc = _km.get_backend(cfg.backend).encode
    recon, resid, codes = jax.vmap(
        lambda xg, cg: enc(xg, cg, cfg.kmeans_chunk))(groups, cents)
    z_tilde = _from_groups(recon, n, d, cfg).astype(z.dtype)
    # keep the stored residual in z.dtype: it is saved by the correction VJP
    # for the backward pass, and an fp32 copy would double that residency
    # for bf16 activations (distortion still accumulates in fp32 first)
    residual = _from_groups(resid, n, d, cfg).astype(z.dtype)
    per_vec = jnp.sum(resid * resid) / jnp.maximum(n, 1)
    return QuantizedBatch(z_tilde.reshape(orig_shape), codes,
                          cents.astype(z.dtype), per_vec,
                          residual.reshape(orig_shape))


def quantize_stateful(z: jax.Array, cfg: PQConfig,
                      state: Optional[QuantizerState] = None,
                      key: Optional[jax.Array] = None
                      ) -> Tuple[QuantizedBatch, QuantizerState]:
    """Warm-start-aware quantize: returns (batch, next round's state).

    ``state=None`` runs the cold path (full seeding + ``kmeans_iters``) and
    bootstraps the state; a prior state runs ``effective_warm_iters`` Lloyd
    iterations from its codebooks. The returned state's codebooks are the
    fp32 Lloyd output (the wire's acked copy is the fp16/delta-reconstructed
    view — see ``federated/wire.encode_pq_delta``)."""
    qb = quantize(z, cfg, key, state=state)
    rounds = jnp.zeros((), jnp.int32) if state is None else state.rounds
    new_state = QuantizerState(codebooks=qb.codebooks.astype(jnp.float32),
                               rounds=rounds + 1)
    return qb, new_state


def quantization_error(z: jax.Array, cfg: PQConfig) -> jax.Array:
    """Mean relative quantization error ‖z−z̃‖/‖z‖ over the batch (for Fig. 3)."""
    resid = quantize(z, cfg).residual
    z2 = z.reshape(-1, z.shape[-1]).astype(jnp.float32)
    r2 = resid.reshape(z2.shape).astype(jnp.float32)
    num = jnp.linalg.norm(r2, axis=-1)
    den = jnp.maximum(jnp.linalg.norm(z2, axis=-1), 1e-12)
    return jnp.mean(num / den)


def vanilla_kmeans_config(num_clusters: int, **kw) -> PQConfig:
    """q=1: quantize whole vectors (paper's 'K-means' baseline)."""
    return PQConfig(num_subvectors=1, num_clusters=num_clusters, num_groups=1, **kw)


def vanilla_pq_config(num_subvectors: int, num_clusters: int, **kw) -> PQConfig:
    """R=q: per-position codebooks (paper's 'vanilla PQ' baseline)."""
    return PQConfig(num_subvectors=num_subvectors, num_clusters=num_clusters,
                    num_groups=num_subvectors, **kw)
