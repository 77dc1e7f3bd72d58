"""Quickstart: FedLite in ~40 lines.

Quantizes a batch of activations with the paper's grouped product quantizer,
shows the compression accounting, and runs one gradient-corrected split
training step on the paper's FEMNIST CNN.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp

from repro.core.compressors import CutState, PQCompressor, uplink
from repro.core.quantizer import PQConfig, quantize
from repro.core.fedlite import TrainState, make_train_step
from repro.data.synthetic import make_federated_image_data
from repro.launch.cache import enable_compile_cache
from repro.models.paper_models import FemnistCNN
from repro.optim import sgd


def main():
    enable_compile_cache()
    # --- 1. the quantizer by itself -----------------------------------------
    z = jax.random.normal(jax.random.PRNGKey(0), (20, 9216))  # B=20, d=9216
    pq = PQConfig(num_subvectors=1152, num_clusters=2)        # paper's 490x pt
    out = quantize(z, pq)
    print(f"compression ratio : {pq.compression_ratio(20, 9216):.1f}x "
          "(paper §5: 490x)")
    print(f"mean sq distortion: {float(out.distortion):.3f}")

    # --- 2. gradient correction (eq. 5) -------------------------------------
    lam = 1e-4
    zt, vjp = jax.vjp(
        lambda x: uplink(x, lam, CutState(), PQCompressor(pq))[0], z)
    (g,) = vjp(jnp.ones_like(z))
    print(f"corrected cotangent == g + λ(z − z̃): "
          f"{bool(jnp.allclose(g, 1.0 + lam * (z - zt), atol=1e-6))}")

    # --- 3. one FedLite training step ---------------------------------------
    data = make_federated_image_data(num_clients=8)
    model = FemnistCNN(pq=pq, lam=lam, client_batch=20)
    opt = sgd(10 ** -1.5)
    step = make_train_step(model, opt, donate=False)
    state = TrainState.create(model.init(jax.random.PRNGKey(0)), opt)
    batch = data.sample_batch(0, jax.random.PRNGKey(1), 20)
    state, metrics = step(state, batch)
    print(f"step 1: loss={float(metrics['loss']):.3f} "
          f"ratio={metrics['pq_compression_ratio']:.0f}x")


if __name__ == "__main__":
    main()
