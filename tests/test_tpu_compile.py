"""Ahead-of-time compiles for a described TPU v5e chip (no chip attached).

The main-path Pallas kernels at real widths, and one whole FEMNIST stacked
train step, are lowered and compiled by the TPU compiler that ships with
libtpu for a described ``v5e:2x2`` topology: a kernel whose blocks Mosaic
refuses, or a step that does not fit 16 GiB, fails here instead of on the
chip. Interpret mode checks none of this.

The topology is described inside module-scoped fixtures of this one file —
never at import — so only the worker that runs these tests loads libtpu.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

HBM_BYTES = 16 * 1024 ** 3
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled(one_chip, monkeypatch):
    """Compile ``fn`` for the described chip from (shape, dtype) pairs; the
    kernels are steered off interpret mode, as on a TPU backend."""
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile()

    return compile_


F32 = jnp.float32
# x (N, 8) subvectors, L centroids: the paper's FEMNIST cohort (10 clients x
# 20 samples x q=1152) and the LM smoke cut (4 x 2048 tokens x 384)
PQ_SHAPES = [(230400, 2), (3145728, 16)]


@pytest.mark.parametrize("n,l", PQ_SHAPES)
@pytest.mark.parametrize("kernel", ["kmeans_assign", "pq_quantize",
                                    "lloyd_update"])
def test_pq_kernel_compiles_for_v5e(compiled, kernel, n, l):
    shapes = [((n, 8), F32), ((l, 8), F32)]
    if kernel == "lloyd_update":
        shapes.append(((n,), F32))
    c = compiled(getattr(ops, kernel), *shapes)
    assert CUSTOM_CALL in c.as_text()


def test_scalar_quantize_compiles_for_v5e(compiled):
    c = compiled(functools.partial(ops.scalar_quantize, bits=8),
                 ((200, 9216), F32), ((), F32), ((), F32))
    assert CUSTOM_CALL in c.as_text()


# the held experts' grouped matmuls of the Moonlight-16B-A3B cell: 16,384
# tokens x top-6 slots, 8 held experts; gate/up (2048 -> 1408), down back
GMM_SHAPES = [(98304, 2048, 1408), (98304, 1408, 2048)]


@pytest.mark.parametrize("m,k,n", GMM_SHAPES)
def test_grouped_matmul_and_vjp_compile_for_v5e(compiled, m, k, n):
    """Forward, the input's gradient and the weights' gradient: the three
    Pallas kernels of one grouped product, each under its own name."""
    bf16 = jnp.bfloat16

    def fwd_and_vjp(lhs, rhs, sizes, ct):
        out, vjp = jax.vjp(lambda a, b: ops.grouped_matmul(
            a, b, sizes, backend="pallas"), lhs, rhs)
        return out, vjp(ct)

    c = compiled(fwd_and_vjp, ((m, k), bf16), ((8, k, n), bf16),
                 ((8,), jnp.int32), ((m, n), bf16))
    text = c.as_text()
    assert text.count(CUSTOM_CALL) == 3
    assert "moe_gmm_kernel" in text and "moe_tgmm_kernel" in text


def test_femnist_stacked_step_compiles_for_v5e(one_chip, monkeypatch):
    """One whole FedLite step of the paper's FEMNIST task — PQ uplink and
    the topk+scalarq downlink both on their Pallas kernels — fits a chip."""
    from repro.core.quantizer import PQConfig
    from repro.data.synthetic import make_federated_image_data
    from repro.federated import FederatedTrainer
    from repro.models.paper_models import FemnistCNN
    from repro.optim import sgd

    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    pq = PQConfig(num_subvectors=1152, num_clusters=2, kmeans_iters=5,
                  backend="pallas")
    trainer = FederatedTrainer(
        FemnistCNN(pq=pq, lam=1e-4, client_batch=20), sgd(10 ** -1.5),
        make_federated_image_data(num_clients=64, seed=0), cohort=10,
        client_batch=20,
        downlink_compressor="chain:topk(k=0.1)+scalarq(bits=8,backend=pallas)")

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    state = on_chip(jax.eval_shape(trainer.init_state, jax.random.PRNGKey(0)))
    batch = on_chip({"image": jax.ShapeDtypeStruct((200, 28, 28, 1), F32),
                     "label": jax.ShapeDtypeStruct((200,), jnp.int32)})
    c = trainer.executor._step.lower(state, batch).compile()
    # PQ Lloyd update + fused encode (uplink) and scalarq (downlink)
    assert c.as_text().count(CUSTOM_CALL) >= 3
    m = c.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES


# the LM cells' attention: StarCoder2-3B (24 query heads over 2 key heads of
# 128) at 2 x 4096, Moonlight's latent attention (16 heads, qk 192, v 128)
# at 2 x 8192
FLASH_SHAPES = [(2, 4096, 24, 2, 128, 128), (2, 8192, 16, 16, 192, 128)]


@pytest.mark.parametrize("b,s,h,kv,hd,vd", FLASH_SHAPES)
def test_flash_attention_and_vjp_compile_for_v5e(compiled, b, s, h, kv, hd,
                                                 vd):
    """The forward, dq and dkv kernels, each under its own name."""
    bf16 = jnp.bfloat16

    def fwd_and_vjp(q, k, v, ct):
        pos = jnp.arange(s)
        out, vjp = jax.vjp(lambda q, k, v: ops.causal_attention(
            q, k, v, pos, pos, scale=hd ** -0.5), q, k, v)
        return out, vjp(ct)

    c = compiled(fwd_and_vjp, ((b, s, h, hd), bf16), ((b, s, kv, hd), bf16),
                 ((b, s, kv, vd), bf16), ((b, s, h, vd), bf16))
    text = c.as_text()
    assert text.count(CUSTOM_CALL) == 3
    assert all(n in text for n in ("flash_attention_fwd", "flash_attention_dq",
                                   "flash_attention_dkv"))
