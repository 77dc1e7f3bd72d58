"""Quantizer backend registry tests (tentpole of the dispatch-layer PR).

jnp vs pallas(-interpret) parity on codes / z̃ / residual, VJP parity under
the gradient correction, "auto" resolution, and the single-K-means-run
invariant of the PQ uplink hook.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kmeans as km
from repro.core.compressors import CutState, PQCompressor, uplink
from repro.core.quantizer import PQConfig, quantize


def pq_uplink(z, lam, cfg):
    """The PQ uplink hook's reconstruction, no carry."""
    return uplink(z, lam, CutState(), PQCompressor(cfg))[0]


def _cfg(backend, **kw):
    base = dict(num_subvectors=4, num_clusters=8, kmeans_iters=6)
    base.update(kw)
    return PQConfig(backend=backend, **base)


# N=60 -> group rows M = 4*60/R: not a multiple of the pallas block (padded);
# N=128 -> M power of two (unpadded for block_n<=512 divisors)
@pytest.mark.parametrize("n", [60, 128])
@pytest.mark.parametrize("r", [1, 2])
def test_jnp_pallas_parity_codes_zt_residual(n, r):
    z = jax.random.normal(jax.random.PRNGKey(n + r), (n, 32))
    qj = quantize(z, _cfg("jnp", num_groups=r))
    qp = quantize(z, _cfg("pallas", num_groups=r))
    np.testing.assert_array_equal(np.asarray(qj.codes), np.asarray(qp.codes))
    np.testing.assert_allclose(qj.dequantized, qp.dequantized,
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(qj.residual, qp.residual, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(float(qj.distortion), float(qp.distortion),
                               rtol=1e-6)


def test_vjp_parity_between_backends():
    """The PQ uplink hook's VJP under pallas == jnp to fp32 tolerance."""
    z = jax.random.normal(jax.random.PRNGKey(3), (48, 16))
    g_in = jax.random.normal(jax.random.PRNGKey(4), (48, 16))
    lam = 0.37
    outs = {}
    for backend in ("jnp", "pallas"):
        zt, vjp = jax.vjp(
            lambda x: pq_uplink(x, lam, _cfg(backend)), z)
        (g_out,) = vjp(g_in)
        outs[backend] = (zt, g_out)
        # eq. (5) must hold within each backend too
        np.testing.assert_allclose(g_out, g_in + lam * (z - zt),
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(outs["jnp"][0], outs["pallas"][0],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(outs["jnp"][1], outs["pallas"][1],
                               rtol=1e-5, atol=1e-6)


def test_auto_resolution_and_registry():
    resolved = km.resolve_backend("auto")
    expected = "pallas" if jax.default_backend() == "tpu" else "jnp"
    assert resolved == expected
    assert km.resolve_backend("jnp") == "jnp"
    assert set(km.available_backends()) >= {"jnp", "pallas", "auto"}
    with pytest.raises(ValueError):
        km.get_backend("nope")
    # auto-backend quantize runs end to end
    z = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    qb = quantize(z, _cfg("auto", num_subvectors=2, num_clusters=4))
    assert qb.dequantized.shape == z.shape


def test_register_custom_backend():
    probe = {"assign": 0}
    jnp_backend = km.get_backend("jnp")

    def counting_assign(x, c):
        probe["assign"] += 1
        return jnp_backend.assign(x, c)

    km.register_backend(km.Backend("probe", counting_assign,
                                   jnp_backend.encode))
    try:
        x = jax.random.normal(jax.random.PRNGKey(1), (64, 8))
        r = km.kmeans(x, 4, 3, backend="probe")
        # fori_loop/scan trace the body once regardless of iteration count
        assert probe["assign"] >= 1
        r_jnp = km.kmeans(x, 4, 3, backend="jnp")
        np.testing.assert_allclose(r.centroids, r_jnp.centroids,
                                   rtol=1e-6, atol=1e-6)
    finally:
        km._REGISTRY.pop("probe", None)


def test_correction_runs_kmeans_exactly_once(monkeypatch):
    """Forward+backward of the PQ uplink hook traces K-means ONCE:
    the residual is emitted by the fused encode and reused by the VJP."""
    calls = {"lloyd": 0, "encode": 0}
    real_lloyd = km.lloyd
    real_get = km.get_backend

    def counting_lloyd(*a, **kw):
        calls["lloyd"] += 1
        return real_lloyd(*a, **kw)

    def counting_get(name="auto"):
        b = real_get(name)

        def encode(x, c, chunk):
            calls["encode"] += 1
            return b.encode(x, c, chunk)

        return km.Backend(b.name, b.assign, encode)

    monkeypatch.setattr(km, "lloyd", counting_lloyd)
    monkeypatch.setattr(km, "get_backend", counting_get)

    z = jax.random.normal(jax.random.PRNGKey(7), (32, 16))
    cfg = _cfg("jnp")
    out, grad = jax.value_and_grad(
        lambda x: jnp.sum(pq_uplink(x, 0.5, cfg) ** 2))(z)
    assert np.isfinite(float(out)) and np.isfinite(np.asarray(grad)).all()
    # one vmapped Lloyd + one vmapped encode across fwd AND bwd (R=1 group)
    assert calls["lloyd"] == 1
    assert calls["encode"] == 1


def test_exact_reconstruction_zero_residual_both_backends():
    """Identical rows must produce a bitwise-zero residual on every backend
    (the FedLite->SplitFed equivalence of tests/test_fedlite.py)."""
    row = jax.random.normal(jax.random.PRNGKey(9), (1, 64))
    z = jnp.tile(row, (8, 1))
    for backend in ("jnp", "pallas"):
        qb = quantize(z, _cfg(backend, num_subvectors=1, num_clusters=2))
        assert float(jnp.abs(qb.residual).max()) == 0.0
        np.testing.assert_array_equal(np.asarray(qb.dequantized),
                                      np.asarray(z))


def test_pq_backend_threaded_from_arch_config():
    from repro.configs.base import get_arch
    from repro.launch.specs import default_pq
    cfg = get_arch("llama3_8b", smoke=True)
    pq = default_pq(cfg)
    assert pq.backend == cfg.pq_backend == "auto"
    pq2 = default_pq(dataclasses.replace(cfg, pq_backend="jnp"))
    assert pq2.backend == "jnp"
