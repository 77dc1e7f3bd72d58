"""Fused Lloyd-update kernel + cross-round warm-start tests (PR 4 tentpole).

Covers: jnp-vs-pallas(interpret) parity of the update statistics and of full
Lloyd runs — including empty clusters and padded tails — the fp32
fixed-point semantics (exact-cover and empty clusters), warm-start reaching
<= cold-start distortion at ``warm_iters`` on stationary inputs, and the
state lifecycle of ``quantize_stateful`` / `PQCompressor.compress_stateful`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kmeans as km
from repro.core.compressors import CutState, PQCompressor, uplink
from repro.core.quantizer import (PQConfig, QuantizerState, quantize,
                                  quantize_stateful)
from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# kernel parity (ops.lloyd_update vs ref.lloyd_update_ref)
# ---------------------------------------------------------------------------

# n=513 exercises the padded tail (not a block multiple); L=5 exercises the
# lane-padded codebook (not a multiple of 8)
@pytest.mark.parametrize("n,d,l", [(64, 8, 4), (513, 8, 5), (128, 16, 16)])
def test_update_kernel_matches_ref(n, d, l):
    x = jax.random.normal(jax.random.PRNGKey(n), (n, d))
    c = jax.random.normal(jax.random.PRNGKey(n + 1), (l, d))
    w = jnp.ones((n,), jnp.float32)
    ds, ct = ops.lloyd_update(x, c, w, block_n=64, interpret=True)
    ds_r, ct_r = ref.lloyd_update_ref(x, w, c, jnp.ones(l))
    np.testing.assert_allclose(np.asarray(ds), np.asarray(ds_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ct), np.asarray(ct_r), rtol=1e-6)


def test_update_kernel_zero_weight_rows_contribute_nothing():
    """Padding rows (weight 0) must contribute exactly 0 — the wrapper's
    internal padding relies on it."""
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 8))
    c = jax.random.normal(jax.random.PRNGKey(1), (4, 8))
    w = jnp.concatenate([jnp.ones(16), jnp.zeros(16)])
    ds, ct = ops.lloyd_update(x, c, w, interpret=True)
    ds_r, ct_r = ref.lloyd_update_ref(x[:16], jnp.ones(16), c, jnp.ones(4))
    np.testing.assert_allclose(np.asarray(ds), np.asarray(ds_r),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(ct), np.asarray(ct_r))


def test_update_kernel_empty_cluster_exact_zero():
    """A centroid no point selects reports count 0 and an exactly-zero
    deviation sum (the caller keeps the previous centroid bitwise)."""
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (64, 4))
    c = jnp.concatenate([jnp.zeros((1, 4)), jnp.full((1, 4), 1e6)])
    ds, ct = ops.lloyd_update(x, c, interpret=True)
    assert float(ct[1]) == 0.0
    assert float(jnp.abs(ds[1]).max()) == 0.0


def test_update_kernel_exact_cover_exact_zero():
    """Members equal to their centroid contribute an exactly-zero update
    (deviation accumulation) — the FedLite == SplitFed invariant."""
    row = jax.random.normal(jax.random.PRNGKey(3), (1, 8))
    x = jnp.tile(row, (16, 1))
    c = jnp.concatenate([row, row + 100.0])
    ds, ct = ops.lloyd_update(x, c, interpret=True)
    assert float(jnp.abs(ds).max()) == 0.0
    np.testing.assert_array_equal(np.asarray(ct), [16.0, 0.0])


# ---------------------------------------------------------------------------
# backend parity of full Lloyd runs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [100, 512])   # 100: padded tail inside chunks
def test_lloyd_backend_parity(n):
    x = jax.random.normal(jax.random.PRNGKey(n), (n, 8))
    c_j = km.lloyd(x, 8, 5, chunk=64, backend="jnp")
    c_p = km.lloyd(x, 8, 5, chunk=64, backend="pallas")
    np.testing.assert_allclose(np.asarray(c_j), np.asarray(c_p),
                               rtol=1e-5, atol=1e-5)


def test_lloyd_backend_parity_with_empty_clusters():
    """Seeding 8 centroids on 2 tight blobs leaves empty clusters; both
    backends must keep them at their previous position bitwise."""
    blobs = jnp.concatenate([jnp.zeros((32, 4)), jnp.full((32, 4), 10.0)])
    init = jnp.stack([jnp.full((4,), v) for v in
                      [0.0, 10.0, 100.0, 200.0]])
    c_j = km.lloyd(blobs, 4, 4, backend="jnp", init_centroids=init)
    c_p = km.lloyd(blobs, 4, 4, backend="pallas", init_centroids=init)
    # the two far-away centroids never get members: kept exactly
    np.testing.assert_array_equal(np.asarray(c_j[2:]), np.asarray(init[2:]))
    np.testing.assert_array_equal(np.asarray(c_p[2:]), np.asarray(init[2:]))
    np.testing.assert_allclose(np.asarray(c_j), np.asarray(c_p),
                               rtol=1e-6, atol=1e-6)


def test_registered_backend_without_update_falls_back_to_scan():
    """A backend registered with no ``update`` slot must keep working via
    the assign-based scan (back-compat for external backends)."""
    b = km.get_backend("jnp")
    km.register_backend(km.Backend("noupdate", b.assign, b.encode))
    try:
        x = jax.random.normal(jax.random.PRNGKey(4), (200, 8))
        c1 = km.lloyd(x, 4, 3, backend="noupdate")
        c2 = km.lloyd(x, 4, 3, backend="jnp")
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    finally:
        km._REGISTRY.pop("noupdate", None)


# ---------------------------------------------------------------------------
# warm-start
# ---------------------------------------------------------------------------

def test_warm_start_zero_iters_returns_init():
    x = jax.random.normal(jax.random.PRNGKey(5), (128, 8))
    init = km.lloyd(x, 4, 3)
    out = km.lloyd(x, 4, 0, init_centroids=init)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(init))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_warm_start_beats_cold_at_warm_iters_stationary(backend):
    """On stationary inputs, warm-starting from a converged codebook at
    ``warm_iters`` must reach <= the distortion of a cold start given the
    same (reduced) iteration budget — the whole point of the reuse."""
    cfg = PQConfig(num_subvectors=4, num_clusters=8, kmeans_iters=6,
                   backend=backend)
    z1 = jax.random.normal(jax.random.PRNGKey(6), (64, 32))
    z2 = jax.random.normal(jax.random.PRNGKey(7), (64, 32))  # same dist
    _, state = quantize_stateful(z1, cfg)
    warm = quantize(z2, cfg, state=state)
    cold_short = quantize(z2, PQConfig(num_subvectors=4, num_clusters=8,
                                       kmeans_iters=cfg.effective_warm_iters,
                                       backend=backend))
    cold_full = quantize(z2, cfg)
    assert float(warm.distortion) <= float(cold_short.distortion) * 1.05
    # and warm at half budget stays in the cold-full ballpark
    assert float(warm.distortion) <= float(cold_full.distortion) * 1.25


def test_quantize_stateful_lifecycle():
    cfg = PQConfig(num_subvectors=2, num_clusters=4, kmeans_iters=4,
                   warm_iters=1)
    assert cfg.effective_warm_iters == 1
    z = jax.random.normal(jax.random.PRNGKey(8), (32, 16))
    qb, s1 = quantize_stateful(z, cfg)
    assert isinstance(s1, QuantizerState)
    assert s1.codebooks.dtype == jnp.float32
    assert s1.codebooks.shape == (1, 4, 8)
    assert int(s1.rounds) == 1
    _, s2 = quantize_stateful(z, cfg, s1)
    assert int(s2.rounds) == 2


def test_default_warm_iters_is_half():
    assert PQConfig(num_subvectors=1, num_clusters=2,
                    kmeans_iters=8).effective_warm_iters == 4
    with pytest.raises(ValueError):
        PQConfig(num_subvectors=1, num_clusters=2, warm_iters=-1)


def test_single_kmeans_run_with_carry_hook(monkeypatch):
    """The warm-start hook preserves the one-kmeans-per-forward+backward
    invariant: Lloyd and the fused encode each trace exactly once."""
    calls = {"lloyd": 0}
    real = km.batched_lloyd

    def counting(*a, **kw):
        calls["lloyd"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(km, "batched_lloyd", counting)
    cfg = PQConfig(num_subvectors=2, num_clusters=4, kmeans_iters=3,
                   backend="jnp")
    comp = PQCompressor(cfg)
    z = jax.random.normal(jax.random.PRNGKey(9), (16, 16))
    state = CutState(quantizer=None, ef_memory=None)

    def loss(a):
        recon, dist, new_state = uplink(a, 0.5, state, comp)
        return jnp.sum(recon ** 2)

    val, grad = jax.value_and_grad(loss)(z)
    assert np.isfinite(float(val)) and np.isfinite(np.asarray(grad)).all()
    assert calls["lloyd"] == 1


def test_carry_hook_correction_and_state():
    """eq.-5 backward + state round counting through the carry hook."""
    cfg = PQConfig(num_subvectors=2, num_clusters=4, kmeans_iters=3)
    comp = PQCompressor(cfg)
    z = jax.random.normal(jax.random.PRNGKey(10), (16, 16))
    lam = 0.7
    (recon, dist, st1), vjp = jax.vjp(
        lambda a: uplink(a, lam, CutState(), comp), z)
    g = jax.random.normal(jax.random.PRNGKey(11), (16, 16))
    (gz,) = vjp((g, jnp.zeros(()), jax.tree.map(jnp.zeros_like, st1)))
    np.testing.assert_allclose(np.asarray(gz),
                               np.asarray(g + lam * (z - recon)),
                               rtol=1e-5, atol=1e-6)
    assert int(st1.quantizer.rounds) == 1
    # warm second round
    _, _, st2 = uplink(z, lam, st1, comp)
    assert int(st2.quantizer.rounds) == 2


def test_carry_hook_error_feedback_telescopes():
    """mem' = (z + mem) − recon; over T rounds the transmitted sum equals
    the input sum + mem_0 − mem_T (exact telescoping, any codec)."""
    from repro.core.compressors import TopKCompressor
    comp = TopKCompressor(k=0.25)
    zs = [jax.random.normal(jax.random.PRNGKey(20 + t), (8, 16))
          for t in range(4)]
    state = CutState(quantizer=None, ef_memory=jnp.zeros((8, 16)))
    sent = jnp.zeros((8, 16))
    for z in zs:
        recon, _, state = uplink(z, 0.0, state, comp)
        sent = sent + recon
    total = sum(zs)
    np.testing.assert_allclose(np.asarray(sent + state.ef_memory),
                               np.asarray(total), rtol=1e-4, atol=1e-5)
