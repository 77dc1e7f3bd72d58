"""Batched K-means in pure JAX, usable *inside* a jitted train step.

FedLite rebuilds codebooks from the current mini-batch at every iteration
(stateless clients, non-IID data), so K-means must be a fixed-shape,
fixed-iteration-count program: ``lax.fori_loop`` over Lloyd iterations,
``lax.scan`` over chunks of points so the one-hot statistics never
materialize an (N, L) tensor for the full batch at once.

Distance computation is expressed as ``‖x‖² − 2·x·Cᵀ + ‖c‖²`` so the inner
product rides the MXU on TPU.

Backend registry
----------------
The assignment / encode primitives are pluggable via a named registry:

  * ``"jnp"``    — pure-jnp ops (XLA fusion; the CPU/testing substrate).
  * ``"pallas"`` — the Pallas kernels in ``repro.kernels``: compiled Mosaic
                   on TPU, interpret mode elsewhere (parity validation).
  * ``"auto"``   — ``"pallas"`` when running on a TPU backend, ``"jnp"``
                   otherwise (interpret-mode Pallas is for correctness, not
                   speed, so it is never auto-selected off-TPU).

A backend bundles the quantizer's compute primitives:

  * ``assign(x, cents) -> codes`` — nearest-centroid assignment, used inside
    the Lloyd iterations (``x`` is a (chunk, D) tile).
  * ``encode(x, cents, chunk) -> (z̃, residual, codes)`` — the fused final
    pass: assignment + centroid gather + residual in one sweep. The Pallas
    implementation (``repro.kernels.pq_quantize``) does one HBM read and two
    writes per element instead of the three separate sweeps the naive path
    takes.
  * ``update(x, weights, cents, chunk) -> (dsums, counts)`` — one Lloyd
    iteration's statistics: assign + deviation-accumulate fused in a single
    HBM sweep (``repro.kernels.lloyd_update`` under the Pallas backend).
    ``None`` (the jnp default, and any backend registered without one) falls
    back to a ``lax.scan`` over chunks built on ``assign``, which
    materializes a (chunk, L) one-hot and re-reads the centroids per step —
    the structure the fused kernel eliminates.

Warm-start: ``lloyd``/``kmeans`` accept ``init_centroids`` to resume from a
previous round's codebook instead of re-seeding — the cross-round codebook
reuse ``core/quantizer.QuantizerState`` builds on (steady-state rounds run
``PQConfig.warm_iters`` ≈ half the cold-start Lloyd iterations).

Numerics: the Lloyd centroid update accumulates *deviations from the current
centroid* (``Σ onehot·(x − c_old)``, then ``c_new = c_old + Σ/count``) rather
than raw coordinate sums. This is algebraically the same mean but loses far
less precision in fp32 — in particular, a cluster whose members all equal its
centroid gets an exactly-zero update, so exact-reconstruction inputs yield an
exactly-zero quantization residual (required by the FedLite → SplitFed
gradient-equivalence property, tests/test_fedlite.py). Empty clusters keep
their previous centroid exactly (``counts == 0`` gates the update). Both
properties hold on every backend: the fused update kernel preserves the
deviation accumulation bit-structure (tests/test_lloyd_update.py).
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


HIGHEST = jax.lax.Precision.HIGHEST


class KMeansResult(NamedTuple):
    centroids: jax.Array  # (L, D)
    codes: jax.Array      # (N,) int32
    distortion: jax.Array  # () mean squared quantization error per point


# ---------------------------------------------------------------------------
# backend registry
# ---------------------------------------------------------------------------

class Backend(NamedTuple):
    """A quantizer compute backend (see module docstring)."""
    name: str
    assign: Callable[[jax.Array, jax.Array], jax.Array]
    encode: Callable[[jax.Array, jax.Array, int],
                     Tuple[jax.Array, jax.Array, jax.Array]]
    # (x, cents, chunk) -> (codes, sqdist); None = derive from encode
    assign_dist: Optional[Callable] = None
    # (x, weights, cents, chunk) -> (dsums, counts); None = scan over assign
    update: Optional[Callable] = None


def _pad_chunks(x: jax.Array, chunk: int):
    """Zero-pad rows to a multiple of ``chunk`` and split into scan tiles.

    Returns ((n_chunks, chunk, D) tiles, real row count n, pad count)."""
    n, d = x.shape
    chunk = min(chunk, max(n, 1))
    pad = (-n) % chunk
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, d), x.dtype)])
    return x.reshape(-1, chunk, d), n, pad


def _assign_jnp(x: jax.Array, centroids: jax.Array) -> jax.Array:
    """codes[i] = argmin_l ‖x_i − c_l‖².  x: (n, D), centroids: (L, D)."""
    # ‖x‖² is constant across l — only the cross term and ‖c‖² matter.
    # HIGHEST: full-f32 distances on TPU too (XLA's default there is one
    # bf16 pass), as the Pallas kernels compute them
    scores = (2.0 * jnp.matmul(x, centroids.T, precision=HIGHEST)
              - jnp.sum(centroids * centroids, axis=-1)[None, :])
    return jnp.argmax(scores, axis=-1).astype(jnp.int32)


def _encode_jnp(x: jax.Array, centroids: jax.Array, chunk: int):
    """Assignment + gather + residual, chunked so scores stay (chunk, L)."""
    d = x.shape[1]
    xc, n, _ = _pad_chunks(x, chunk)

    def body(_, xb):
        codes = _assign_jnp(xb, centroids)
        zt = centroids[codes]
        return None, (zt, xb - zt, codes)

    _, (zt, resid, codes) = jax.lax.scan(body, None, xc)
    return (zt.reshape(-1, d)[:n], resid.reshape(-1, d)[:n],
            codes.reshape(-1)[:n])


def _assign_dist_jnp(x: jax.Array, centroids: jax.Array, chunk: int):
    """codes + per-point squared distances, without materializing z̃."""
    xc, n, _ = _pad_chunks(x, chunk)

    def body(_, xb):
        codes = _assign_jnp(xb, centroids)
        err = jnp.sum(jnp.square(xb - centroids[codes]), axis=-1)
        return None, (codes, err)

    _, (codes, err) = jax.lax.scan(body, None, xc)
    return codes.reshape(-1)[:n], err.reshape(-1)[:n]


def _assign_pallas(x: jax.Array, centroids: jax.Array) -> jax.Array:
    from repro.kernels import ops  # deferred: kernels must stay optional here
    codes, _ = ops.kmeans_assign(x, centroids)
    return codes


def _encode_pallas(x: jax.Array, centroids: jax.Array, chunk: int):
    from repro.kernels import ops
    zt, resid, codes = ops.pq_quantize(x, centroids)
    return zt.astype(jnp.float32), resid, codes


def _assign_dist_pallas(x: jax.Array, centroids: jax.Array, chunk: int):
    # the assign kernel already emits distances — no z̃ HBM write
    from repro.kernels import ops
    return ops.kmeans_assign(x, centroids)


def _update_scan(assign, x, weights, centroids, chunk):
    """Fallback Lloyd-update: scan over chunks on top of ``assign``.

    This is the pre-kernel structure: per scan step XLA materializes a
    (chunk, L) one-hot and re-reads the centroids for the deviation gather.
    Bitwise-identical to the historical in-``lloyd`` accumulation."""
    L, d = centroids.shape
    xc = x.reshape(-1, min(chunk, max(x.shape[0], 1)), d)  # x pre-padded
    wc = weights.reshape(xc.shape[0], -1)

    def acc(carry, inp):
        dsums, counts = carry
        xb, wb = inp
        codes = assign(xb, centroids)
        onehot = jax.nn.one_hot(codes, L, dtype=jnp.float32) * wb[:, None]
        # deviation accumulation: exact-cover clusters contribute 0
        delta = xb - centroids[codes]
        return (dsums + jnp.matmul(onehot.T, delta, precision=HIGHEST),
                counts + onehot.sum(axis=0)), None

    (dsums, counts), _ = jax.lax.scan(
        acc, (jnp.zeros((L, d), jnp.float32), jnp.zeros((L,), jnp.float32)),
        (xc, wc))
    return dsums, counts


def _update_pallas(x: jax.Array, weights: jax.Array, centroids: jax.Array,
                   chunk: int):
    from repro.kernels import ops
    return ops.lloyd_update(x, centroids, weights)


_REGISTRY: Dict[str, Backend] = {
    "jnp": Backend("jnp", _assign_jnp, _encode_jnp, _assign_dist_jnp),
    "pallas": Backend("pallas", _assign_pallas, _encode_pallas,
                      _assign_dist_pallas, _update_pallas),
}


def register_backend(backend: Backend) -> None:
    """Register (or replace) a named backend."""
    _REGISTRY[backend.name] = backend


def available_backends() -> Tuple[str, ...]:
    return tuple(_REGISTRY) + ("auto",)


def resolve_backend(name: str = "auto") -> str:
    """Resolve "auto" to a concrete registered backend name."""
    if name == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return name


def get_backend(name: str = "auto") -> Backend:
    resolved = resolve_backend(name)
    try:
        return _REGISTRY[resolved]
    except KeyError:
        raise ValueError(
            f"unknown quantizer backend {name!r} (resolved {resolved!r}); "
            f"registered: {sorted(_REGISTRY)}") from None


# ---------------------------------------------------------------------------
# Lloyd iterations
# ---------------------------------------------------------------------------

def _init_centroids(x: jax.Array, num_clusters: int,
                    key: Optional[jax.Array]) -> jax.Array:
    """Farthest-point / k-means++ seeding on a strided subsample.

    Plain strided or uniform-random seeding regularly drops a true cluster and
    Lloyd cannot recover (empty-cluster local minimum). FPS guarantees spread
    seeds at O(L·M·D) cost on an M = O(L) subsample — negligible next to one
    Lloyd iteration over the full batch. With a PRNG key the selection becomes
    kmeans++ (D² sampling); without, it is deterministic farthest-point.
    """
    n, d = x.shape
    L = num_clusters
    m = min(n, max(4 * L, 256))
    xs = x[:: max(n // m, 1)][:m]
    m = xs.shape[0]

    cents0 = jnp.zeros((L, d), x.dtype).at[0].set(xs[0])
    mind0 = jnp.sum(jnp.square(xs - xs[0][None, :]), axis=-1)

    if key is None:
        def body(l, state):
            cents, mind = state
            idx = jnp.argmax(mind)
            c = xs[idx]
            cents = cents.at[l].set(c)
            mind = jnp.minimum(mind,
                               jnp.sum(jnp.square(xs - c[None, :]), axis=-1))
            return cents, mind
        cents, _ = jax.lax.fori_loop(1, L, body, (cents0, mind0))
    else:
        keys = jax.random.split(key, L)

        def body(l, state):
            cents, mind = state
            logits = jnp.log(jnp.maximum(mind, 1e-30))
            idx = jax.random.categorical(keys[l], logits)
            c = xs[idx]
            cents = cents.at[l].set(c)
            mind = jnp.minimum(mind,
                               jnp.sum(jnp.square(xs - c[None, :]), axis=-1))
            return cents, mind
        cents, _ = jax.lax.fori_loop(1, L, body, (cents0, mind0))
    return cents


def lloyd(x: jax.Array, num_clusters: int, num_iters: int = 8, *,
          key: Optional[jax.Array] = None, chunk: int = 4096,
          backend: str = "jnp",
          init_centroids: Optional[jax.Array] = None) -> jax.Array:
    """Lloyd iterations only: returns fp32 centroids (L, D), no final assign.

    ``init_centroids`` (L, D) warm-starts the iterations from a previous
    round's codebook instead of FPS/kmeans++ seeding — the cross-round
    reuse path (``num_iters`` is then typically ``PQConfig.warm_iters``;
    ``num_iters=0`` returns the initializer unchanged).

    Each iteration's statistics come from the backend's fused ``update``
    (one HBM sweep under Pallas) or the ``assign``-based scan fallback. The
    centroid update is accumulated as deviations from the current centroids
    (see module docstring) so clusters that exactly cover their points are
    fixed points of the update in fp32, not just in exact arithmetic.
    """
    x = x.astype(jnp.float32)
    n, d = x.shape
    L = num_clusters
    b = get_backend(backend)

    # pad N up to a multiple of chunk; padded rows carry zero weight
    xc, n, n_pad = _pad_chunks(x, chunk)
    weights = jnp.concatenate(
        [jnp.ones((n,), jnp.float32), jnp.zeros((n_pad,), jnp.float32)])
    x_flat = xc.reshape(-1, d)
    chunk_eff = xc.shape[1]

    if init_centroids is not None:
        cents0 = init_centroids.astype(jnp.float32)
        if cents0.shape != (L, d):
            raise ValueError(
                f"init_centroids {cents0.shape} != ({L}, {d})")
    else:
        cents0 = _init_centroids(x, L, key)

    def lloyd_iter(_, cents):
        if b.update is not None:
            dsums, counts = b.update(x_flat, weights, cents, chunk_eff)
        else:
            dsums, counts = _update_scan(b.assign, x_flat, weights,
                                         cents, chunk_eff)
        # empty clusters keep their previous centroid
        return cents + jnp.where(counts[:, None] > 0,
                                 dsums / jnp.maximum(counts[:, None], 1.0),
                                 0.0)

    return jax.lax.fori_loop(0, num_iters, lloyd_iter, cents0)


def kmeans(x: jax.Array, num_clusters: int, num_iters: int = 8, *,
           key: Optional[jax.Array] = None, chunk: int = 4096,
           backend: str = "jnp",
           init_centroids: Optional[jax.Array] = None) -> KMeansResult:
    """Lloyd's algorithm with a fixed iteration count.

    Args:
      x: (N, D) points. Computation runs in fp32 regardless of input dtype.
      num_clusters: L.
      num_iters: Lloyd iterations (static).
      key: optional PRNG key for random init; None = deterministic strided.
      chunk: points per scan step for the assign/accumulate pass.
      backend: "jnp" | "pallas" | "auto" (see module docstring).
      init_centroids: optional (L, D) warm-start codebook (skips seeding).
    Returns:
      KMeansResult(centroids (L, D) in x.dtype, codes (N,) int32, distortion).
    """
    in_dtype = x.dtype
    xf = x.astype(jnp.float32)
    n = xf.shape[0]
    cents = lloyd(xf, num_clusters, num_iters, key=key, chunk=chunk,
                  backend=backend, init_centroids=init_centroids)
    b = get_backend(backend)
    if b.assign_dist is not None:
        codes, sqdist = b.assign_dist(xf, cents, chunk)
    else:  # backend without a distance pass: derive from encode
        _, resid, codes = b.encode(xf, cents, chunk)
        sqdist = jnp.sum(resid * resid, axis=-1)
    distortion = jnp.sum(sqdist) / jnp.maximum(n, 1)
    return KMeansResult(cents.astype(in_dtype), codes, distortion)


@functools.partial(jax.jit, static_argnums=(1, 2))
def kmeans_jit(x, num_clusters, num_iters):
    return kmeans(x, num_clusters, num_iters)


def _vmap_groups(per_group_fn, x, key, init=None, **kw):
    fn = functools.partial(per_group_fn, **kw)
    keys = None if key is None else jax.random.split(key, x.shape[0])
    if init is None and keys is None:
        return jax.vmap(lambda g: fn(g))(x)
    if init is None:
        return jax.vmap(lambda g, k: fn(g, key=k))(x, keys)
    if keys is None:
        return jax.vmap(lambda g, c: fn(g, init_centroids=c))(x, init)
    return jax.vmap(
        lambda g, k, c: fn(g, key=k, init_centroids=c))(x, keys, init)


def batched_lloyd(x: jax.Array, num_clusters: int, num_iters: int = 8, *,
                  key: Optional[jax.Array] = None, chunk: int = 4096,
                  backend: str = "jnp",
                  init_centroids: Optional[jax.Array] = None) -> jax.Array:
    """vmapped ``lloyd`` over a leading group axis. x: (G, N, D) -> (G, L, D).
    ``init_centroids``: optional (G, L, D) per-group warm-start codebooks."""
    return _vmap_groups(lloyd, x, key, init_centroids,
                        num_clusters=num_clusters, num_iters=num_iters,
                        chunk=chunk, backend=backend)


def batched_kmeans(x: jax.Array, num_clusters: int, num_iters: int = 8, *,
                   key: Optional[jax.Array] = None, chunk: int = 4096,
                   backend: str = "jnp",
                   init_centroids: Optional[jax.Array] = None):
    """vmapped kmeans over a leading group axis.  x: (G, N, D)."""
    return _vmap_groups(kmeans, x, key, init_centroids,
                        num_clusters=num_clusters, num_iters=num_iters,
                        chunk=chunk, backend=backend)
