"""Split-learning fine-tune of a (reduced) assigned LLM with FedLite.

Demonstrates the framework on the transformer zoo: pick any --arch from the
assigned list; its reduced (smoke) variant trains for a few hundred steps on
synthetic non-IID federated text with the cut-layer PQ + gradient
correction. Each sequence is one client (per-client codebooks), exactly as
the production mesh maps cohorts to data shards.

    PYTHONPATH=src python examples/split_llm_finetune.py \
        --arch llama3_8b --steps 200
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.base import ARCH_IDS, get_arch
from repro.core.fedlite import TrainState, comm_report, make_train_step
from repro.core.quantizer import PQConfig
from repro.data.synthetic import make_federated_lm_data
from repro.launch.cache import enable_compile_cache
from repro.launch.specs import make_model
from repro.optim import get_optimizer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3_8b", choices=ARCH_IDS)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lam", type=float, default=1e-4)
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch, smoke=True)
    if cfg.family in ("vlm", "audio"):
        raise SystemExit("use the text archs for this example; see "
                         "tests/test_archs.py for vlm/audio batches")
    model = make_model(cfg, lam=args.lam)
    opt = get_optimizer("adam", args.lr)
    step = make_train_step(model, opt, donate=False)
    state = TrainState.create(model.init(jax.random.PRNGKey(0)), opt)

    data = make_federated_lm_data(num_clients=32, vocab=cfg.vocab_size,
                                  seed=0)
    rep = comm_report(model, state.params, tokens_per_client=args.seq)
    print(f"{args.arch} (reduced): client params "
          f"{rep['fedlite_uplink_bits'] / 8e6:.2f} MB uplink/iter vs "
          f"splitfed {rep['splitfed_uplink_bits'] / 8e6:.2f} MB "
          f"({rep['activation_compression_ratio']:.0f}x activation compression)")

    t0 = time.time()
    for s in range(args.steps):
        # one cohort: each sequence is a distinct client's minibatch
        parts = [data.sample_batch(c, jax.random.fold_in(
            jax.random.PRNGKey(s), c), 1, seq=args.seq)
            for c in range(args.batch)]
        batch = jax.tree.map(lambda *xs: jnp.concatenate(xs), *parts)
        state, m = step(state, batch)
        if s % 20 == 0 or s == args.steps - 1:
            print(f"step {s:4d}  loss={float(m['loss']):.4f}  "
                  f"ce={float(m['ce']):.4f}  "
                  f"distortion={float(m.get('pq_distortion', 0)):.3f}  "
                  f"({time.time() - t0:.0f}s)")


if __name__ == "__main__":
    main()
