"""Gradient-correction tests (paper §4.2 / Appendix A)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.compressors import CutState, PQCompressor, downlink, uplink
from repro.core.quantizer import PQConfig, quantize


CFG = PQConfig(num_subvectors=4, num_clusters=4, kmeans_iters=8)


def pq_uplink(z, lam, cfg):
    """The PQ uplink hook's reconstruction, no carry."""
    return uplink(z, lam, CutState(), PQCompressor(cfg))[0]


def test_forward_equals_plain_quantize():
    z = jax.random.normal(jax.random.PRNGKey(0), (32, 16))
    zt = pq_uplink(z, 0.1, CFG)
    np.testing.assert_allclose(zt, quantize(z, CFG).dequantized, rtol=1e-6)


@pytest.mark.parametrize("lam", [0.0, 1e-4, 0.5])
def test_vjp_is_eq5(lam):
    """cotangent(z) == g + λ(z − z̃) exactly (paper eq. 5)."""
    z = jax.random.normal(jax.random.PRNGKey(1), (16, 16))
    g_in = jax.random.normal(jax.random.PRNGKey(2), (16, 16))
    zt, vjp = jax.vjp(lambda x: pq_uplink(x, lam, CFG), z)
    (g_out,) = vjp(g_in)
    expected = g_in + lam * (z - zt)
    np.testing.assert_allclose(g_out, expected, rtol=1e-5, atol=1e-6)


def test_surrogate_loss_equivalence():
    """Appendix A: the corrected gradient is the gradient of
    ‖z−ẑ‖² + (λ/2)‖z−z̃‖² with ẑ = z − g/2 and z̃ fixed."""
    lam = 0.3
    z = jax.random.normal(jax.random.PRNGKey(3), (8, 16))
    g = jax.random.normal(jax.random.PRNGKey(4), (8, 16))
    zt = quantize(z, CFG).dequantized
    z_hat = jax.lax.stop_gradient(z - g / 2)
    zt_f = jax.lax.stop_gradient(zt)

    def surrogate(x):
        return (jnp.sum((x - z_hat) ** 2) + lam / 2 * jnp.sum((x - zt_f) ** 2))

    grad_s = jax.grad(surrogate)(z)
    # eq. (5) cotangent with incoming g
    _, vjp = jax.vjp(lambda x: pq_uplink(x, lam, CFG), z)
    (g_corrected,) = vjp(g)
    np.testing.assert_allclose(grad_s, g_corrected, rtol=1e-4, atol=1e-5)


def test_lambda_zero_is_straight_through():
    z = jax.random.normal(jax.random.PRNGKey(5), (16, 16))
    g = jnp.ones_like(z)
    _, vjp = jax.vjp(lambda x: pq_uplink(x, 0.0, CFG), z)
    (g_out,) = vjp(g)
    np.testing.assert_allclose(g_out, g)


def test_correction_pulls_toward_lower_quantization_error():
    """Gradient descent on 0 loss with λ>0 reduces ‖z−z̃‖ (the regularizer
    effect of eq. 6): moving z along -λ(z−z̃) shrinks the residual."""
    z = jax.random.normal(jax.random.PRNGKey(6), (64, 16)) * 3
    lam = 1.0
    zt = quantize(z, CFG).dequantized
    err0 = float(jnp.mean(jnp.sum((z - zt) ** 2, -1)))
    _, vjp = jax.vjp(lambda x: pq_uplink(x, lam, CFG), z)
    (g,) = vjp(jnp.zeros_like(z))       # pure correction term
    z2 = z - 0.5 * g
    zt2 = quantize(z2, CFG).dequantized
    err1 = float(jnp.mean(jnp.sum((z2 - zt2) ** 2, -1)))
    assert err1 < err0


def test_downlink_quantization():
    """Beyond-paper: identity forward, PQ-compressed cotangent backward."""
    z = jax.random.normal(jax.random.PRNGKey(8), (32, 16))
    g_in = jax.random.normal(jax.random.PRNGKey(9), (32, 16))
    out, vjp = jax.vjp(
        lambda x: downlink(x, None, None, PQCompressor(CFG)), z)
    np.testing.assert_array_equal(out, z)          # identity forward
    (g_out,) = vjp(g_in)
    expected = quantize(g_in, CFG).dequantized
    np.testing.assert_allclose(g_out, expected, rtol=1e-5, atol=1e-6)
    # the compressed gradient is close to (but not equal to) the raw one
    assert not np.allclose(g_out, g_in)
    rel = np.linalg.norm(g_out - g_in) / np.linalg.norm(g_in)
    assert rel < 0.9


def test_downlink_in_model_trains():
    from repro.configs.base import get_arch
    from repro.core.quantizer import PQConfig
    from repro.models.transformer import TransformerLM
    from repro.data.synthetic import make_lm_batch
    cfg = get_arch("llama3_8b", smoke=True)
    pq = PQConfig(num_subvectors=cfg.d_model // 8, num_clusters=4,
                  kmeans_iters=3)
    model = TransformerLM(cfg, pq=pq, lam=1e-4,
                          downlink_compressor=PQCompressor(pq))
    params = model.init(jax.random.PRNGKey(0))
    batch = make_lm_batch(jax.random.PRNGKey(1), 2, 32, cfg.vocab_size)
    g = jax.grad(lambda p: model.loss(p, batch)[0])(params)
    for leaf in jax.tree.leaves(g):
        assert np.isfinite(np.asarray(leaf)).all()
    # client grads nonzero through the doubly-compressed link
    gn = float(jnp.linalg.norm(g["client"]["layers"]["p0"]["mixer"]["wq"]))
    assert gn > 0
