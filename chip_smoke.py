#!/usr/bin/env python3
"""On-chip smoke run of the FedLite split-learning trainer.

    python chip_smoke.py             # one TPU chip: phases A and B
    python chip_smoke.py --chips 4   # four chips: phase A, mesh vs stacked

Phase A — the paper's FEMNIST task at paper widths: `FemnistCNN` cut at
d=9216, PQ q=1152 / L=2 / 5 Lloyd iterations, λ=1e-4, 64 synthetic clients,
cohort 10 x 20 samples, SGD at 10^-1.5, downlink
``chain:topk(k=0.1)+scalarq(bits=8)``, 5 rounds through `FederatedTrainer`
on the Pallas kernels, then the same rounds on the pure-jnp backends.

Phase B — an LM at published widths: StarCoder2-3B (d_model 3072, 24/2
heads of 128, d_ff 12288, vocab 49152, bf16, 4 client periods), depth cut
to the smallest that keeps 2 server layers, 4 clients x 1 x 2048 tokens,
Adam, 3 rounds through `FederatedTrainer`.

``--chips 4`` runs only phase A with ``executor="mesh"`` over four chips
and the same rounds on the stacked executor on one chip, in this process.

Every time printed is a smoke timing on this run, not a benchmark. The
last line is one JSON object ``{"ok": true, "device": {...}}``; any failed
check or phase exits non-zero without it. Nothing here falls back to the
CPU or to interpret mode, and no child process is started.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'
FEMNIST_ROUNDS = 5
LM_ROUNDS = 3
# Pallas-vs-jnp loss band for phase A. On CPU the backends agree to 1e-4
# (typically 1e-6). On the TPU both quantizer backends compute distances at
# HIGHEST precision, but the rest of the step runs XLA's default matmul
# precision (one bf16 pass), and a near-tie assignment that flips between
# the kernel's and the scan's f32 summation order reaches the loss through
# those coarser layers for 5 rounds of SGD: 10x the CPU band.
PALLAS_JNP_BAND = 1e-3
# mesh-vs-stacked band of tests/test_executor.py: float reassociation only,
# in f32. Both legs of that comparison run at HIGHEST matmul precision: at
# the TPU default (one bf16 pass) the fused cohort step and the per-client
# shard step round different intermediates to bf16, and the first update
# already moved the next loss by 1.7e-3 relative on four v5e chips.
MESH_RTOL = 5e-4
# StarCoder2-3B keeps 4 periods (4 layers) on the client; 2 server layers is
# the least that still has a server stack of more than one layer. At this
# depth the compiled step needs 11.98 GiB of a v5e chip's 16 GiB (the
# v5e:2x2 ahead-of-time compile's memory_analysis, donated state); the next
# depth adds ~0.75 GiB of bf16 weights + f32 Adam moments per layer.
LM_DEPTH = 6


def fail(msg: str):
    raise SystemExit(f"chip_smoke FAILED: {msg}")


def device_line(devices):
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def run_rounds(trainer, rounds: int, seed: int = 0):
    """Compile the trainer's synchronous step ahead of time (timed, then
    inspected), then run ``rounds`` rounds through ``trainer.round``."""
    import jax
    key = jax.random.PRNGKey(seed)
    state = trainer.init_state(key)
    parts = [trainer.client_batch_for(c, key) for c in range(trainer.cohort)]
    t0 = time.perf_counter()
    compiled = trainer.executor.lower(state, parts).compile()
    compile_s = time.perf_counter() - t0
    del parts
    losses = []
    t0 = time.perf_counter()
    for r in range(rounds):
        state, metrics = trainer.round(state, jax.random.fold_in(
            jax.random.PRNGKey(seed + 1), r))
        losses.append(metrics["loss"])
    losses = [float(x) for x in losses]      # one host sync, at the end
    run_s = time.perf_counter() - t0
    return state, {"compile_s": compile_s, "run_s": run_s, "losses": losses,
                   "custom_calls": compiled.as_text().count(CUSTOM_CALL),
                   "compiled": compiled}


def femnist_trainer(backend: str, executor: str):
    from repro.core.quantizer import PQConfig
    from repro.data.synthetic import make_federated_image_data
    from repro.federated import FederatedTrainer
    from repro.models.paper_models import FemnistCNN
    from repro.optim import sgd
    pq = PQConfig(num_subvectors=1152, num_clusters=2, kmeans_iters=5,
                  backend=backend)
    return FederatedTrainer(
        FemnistCNN(pq=pq, lam=1e-4, client_batch=20), sgd(10 ** -1.5),
        make_federated_image_data(num_clients=64, seed=0), cohort=10,
        client_batch=20, executor=executor,
        downlink_compressor="chain:topk(k=0.1)+scalarq(bits=8,backend="
                            f"{backend})")


def report(phase: str, res: dict, device):
    stats = device.memory_stats() or {}
    print(f"[{phase}] smoke timing, not a benchmark: compile "
          f"{res['compile_s']:.2f} s, {len(res['losses'])} rounds "
          f"{res['run_s']:.2f} s")
    print(f"[{phase}] losses {res['losses']}")
    print(f"[{phase}] tpu_custom_call in compiled step: "
          f"{res['custom_calls']}")
    print(f"[{phase}] peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
          f"on {device.device_kind}")


def check_losses(phase: str, losses):
    if not all(math.isfinite(x) for x in losses):
        fail(f"{phase}: non-finite loss {losses}")


def phase_a(device):
    _, pallas = run_rounds(femnist_trainer("pallas", "stacked"),
                           FEMNIST_ROUNDS)
    report("A pallas", pallas, device)
    _, ref = run_rounds(femnist_trainer("jnp", "stacked"), FEMNIST_ROUNDS)
    report("A jnp", ref, device)
    check_losses("A pallas", pallas["losses"])
    check_losses("A jnp", ref["losses"])
    if pallas["custom_calls"] == 0:
        fail("A: the Pallas step holds no tpu_custom_call")
    gap = max(abs(a - b) for a, b in zip(pallas["losses"], ref["losses"]))
    print(f"[A] pallas-vs-jnp max |loss gap| {gap:.3e} "
          f"(band {PALLAS_JNP_BAND:g})")
    if not gap <= PALLAS_JNP_BAND:
        fail(f"A: pallas-vs-jnp loss gap {gap:.3e} > {PALLAS_JNP_BAND:g}")


def phase_b(device):
    from repro.configs.base import get_arch
    from repro.data.synthetic import make_federated_lm_data
    from repro.federated import FederatedTrainer
    from repro.launch.specs import make_model
    from repro.optim import get_optimizer
    cfg = dataclasses.replace(get_arch("starcoder2_3b"), num_layers=LM_DEPTH)
    print(f"[B] {cfg.name} at depth {cfg.num_layers} "
          f"({cfg.cut_periods} client + "
          f"{cfg.num_layers - cfg.cut_periods} server layers): the smallest "
          "depth with 2 server layers; it needs 11.98 GiB of 16 GiB in the "
          "v5e:2x2 ahead-of-time compile")
    trainer = FederatedTrainer(
        make_model(cfg), get_optimizer("adam", 1e-4),
        make_federated_lm_data(num_clients=4, vocab=cfg.vocab_size, seed=0),
        cohort=4, client_batch=1, batch_kwargs={"seq": 2048})
    _, res = run_rounds(trainer, LM_ROUNDS)
    report("B", res, device)
    check_losses("B", res["losses"])
    if res["custom_calls"] == 0:
        fail("B: the compiled step holds no tpu_custom_call")


def phase_a_mesh(devices):
    import jax
    with jax.default_matmul_precision("highest"):
        state, mesh = run_rounds(femnist_trainer("pallas", "mesh"),
                                 FEMNIST_ROUNDS)
        _, stacked = run_rounds(femnist_trainer("pallas", "stacked"),
                                FEMNIST_ROUNDS)
    report("A mesh x4", mesh, devices[0])
    ids = {d.id for d in devices}
    spanned = {d.id for d in jax.tree.leaves(state.params)[0].sharding
               .device_set}
    # the step's client-major batch: every leaf split over all 4 devices,
    # one equal slice of the (padded) cohort on each
    compiled = mesh["compiled"]
    batch_sh = jax.tree.leaves(compiled.input_shardings[0][1])
    batch_av = jax.tree.leaves(compiled.in_avals[0][1])
    sliced = [({d.id for d in sh.device_set} == ids
               and sh.shard_shape(av.shape)[0] * 4 == av.shape[0])
              for sh, av in zip(batch_sh, batch_av)]
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
              for d in devices]
    print(f"[A mesh x4] state on devices {sorted(spanned)}; batch leaves "
          f"split 4 ways: {sum(sliced)}/{len(sliced)}; bytes_in_use per "
          f"device {in_use}")
    del state
    report("A stacked x1", stacked, devices[0])
    for name, res in (("A mesh x4", mesh), ("A stacked x1", stacked)):
        check_losses(name, res["losses"])
        if res["custom_calls"] == 0:
            fail(f"{name}: the compiled step holds no tpu_custom_call")
    if spanned != ids:
        fail(f"mesh state spans devices {sorted(spanned)}, not all 4")
    if not sliced or not all(sliced):
        fail("the mesh step's batch is not split over all 4 devices")
    if not all(b > 0 for b in in_use):
        fail(f"a device holds nothing: bytes_in_use {in_use}")
    rel = max(abs(a - b) / abs(b) for a, b in zip(mesh["losses"],
                                                  stacked["losses"]))
    print(f"[A] mesh-vs-stacked max relative loss gap {rel:.3e} "
          f"(rtol {MESH_RTOL:g})")
    if not rel <= MESH_RTOL:
        fail(f"mesh-vs-stacked loss gap {rel:.3e} > rtol {MESH_RTOL:g}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax
    from repro.launch.cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, JAX found "
             f"{len(devices)}")
    devices = devices[:args.chips]
    print(f"devices: {len(devices)} x {devices[0].device_kind}; "
          f"compile cache {cache_dir}")
    if args.chips == 4:
        phase_a_mesh(devices)
    else:
        phase_a(devices[0])
        phase_b(devices[0])
    print(json.dumps({"ok": True, "device": device_line(devices)}))


if __name__ == "__main__":
    main()
