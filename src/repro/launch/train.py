"""Production training launcher.

Runs FedLite split training for any assigned architecture on the installed
device topology. On real hardware this runs under the production mesh
(launch/mesh.py); on this CPU container use --smoke for the reduced configs
(the full configs are exercised via launch/dryrun.py).

  PYTHONPATH=src python -m repro.launch.train --arch llama3_8b --smoke \
      --steps 100 --batch 8 --seq 128
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpointing import latest_step, restore_checkpoint, save_checkpoint
from repro.configs.base import ARCH_IDS, get_arch
from repro.core.fedlite import TrainState, comm_report, make_train_step
from repro.data.synthetic import make_lm_batch
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import default_pq, make_model
from repro.optim import get_optimizer, warmup_cosine
from repro.sharding import use_mesh
from repro.sharding.rules import param_shardings


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--lam", type=float, default=1e-4)
    ap.add_argument("--no-pq", action="store_true", help="SplitFed baseline")
    ap.add_argument("--mesh", choices=["none", "single", "multi"],
                    default="none")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch, smoke=args.smoke)
    mesh = None if args.mesh == "none" else make_production_mesh(
        multi_pod=args.mesh == "multi")

    with use_mesh(mesh):
        model = make_model(cfg, with_pq=not args.no_pq, lam=args.lam)
        opt = get_optimizer(cfg.optimizer if not args.smoke else "adam",
                            warmup_cosine(args.lr, 10, args.steps))
        step_fn = make_train_step(model, opt, quantize=not args.no_pq)

        params = model.init(jax.random.PRNGKey(0))
        if mesh is not None:
            params = jax.tree.map(
                lambda x, s: jax.device_put(x, s) if s is not None else x,
                params, param_shardings(params, mesh))
        state = TrainState.create(params, opt)
        start = 0
        if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
            start = latest_step(args.ckpt_dir)
            state = TrainState(
                params=restore_checkpoint(args.ckpt_dir, start)["params"],
                opt_state=state.opt_state, step=jnp.asarray(start))
            print(f"resumed from step {start}")

        rep = comm_report(model, state.params, tokens_per_client=args.seq)
        if "activation_compression_ratio" in rep:
            print(f"uplink compression: "
                  f"{rep['activation_compression_ratio']:.0f}x activations, "
                  f"{rep['uplink_reduction_vs_splitfed']:.1f}x total vs SplitFed")

        def make_batch(key):
            if cfg.num_codebooks > 1:   # audio: (B, K, S) token grids
                t = jax.random.randint(key, (args.batch, cfg.num_codebooks,
                                             args.seq), 0, cfg.vocab_size)
                return {"tokens": t, "labels": t}
            if cfg.family == "vlm":     # stubbed patch embeddings + text
                k1, k2 = jax.random.split(key)
                s_vis = args.seq // 4
                s_txt = args.seq - s_vis
                pos = jnp.broadcast_to(
                    jnp.arange(args.seq, dtype=jnp.int32),
                    (3, args.batch, args.seq))
                toks = jax.random.randint(k1, (args.batch, s_txt), 0,
                                          cfg.vocab_size)
                return {
                    "tokens": toks,
                    "vision_embeds": jax.random.normal(
                        k2, (args.batch, s_vis, cfg.vision_embed_dim)),
                    "positions": pos,
                    "labels": jnp.concatenate(
                        [jnp.full((args.batch, s_vis), -1, jnp.int32),
                         toks], axis=1),
                }
            return make_lm_batch(key, args.batch, args.seq, cfg.vocab_size)

        t0 = time.time()
        for s in range(start, args.steps):
            batch = make_batch(jax.random.fold_in(jax.random.PRNGKey(1), s))
            state, m = step_fn(state, batch)
            if s % args.log_every == 0 or s == args.steps - 1:
                print(f"step {s:5d}  loss={float(m['loss']):.4f}  "
                      f"ce={float(m['ce']):.4f}  "
                      f"{(time.time() - t0):.0f}s")
            if args.ckpt_dir and args.ckpt_every and \
                    (s + 1) % args.ckpt_every == 0:
                save_checkpoint(args.ckpt_dir, s + 1, {"params": state.params})
        print("done")


if __name__ == "__main__":
    main()
