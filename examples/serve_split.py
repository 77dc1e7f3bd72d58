"""Split serving with compressed uplink: batched prefill + decode.

The client side computes the prompt's cut-layer activations, compresses them
with the grouped PQ (the inference uplink is exactly the paper's B·d
message), and the server side completes prefill and serves decode steps
against the KV/SSM caches. Run with any assigned arch (reduced variant):

    PYTHONPATH=src python examples/serve_split.py --arch mamba2_1p3b \
        --prompt-len 48 --gen 16 --batch 4
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.base import ARCH_IDS, get_arch
from repro.launch.cache import enable_compile_cache
from repro.launch.specs import make_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2_3b", choices=ARCH_IDS)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--no-compress", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch, smoke=True)
    if cfg.family in ("vlm",):
        raise SystemExit("text archs only in this example")
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, P, G = args.batch, args.prompt_len, args.gen

    if cfg.num_codebooks > 1:
        prompt = jax.random.randint(jax.random.PRNGKey(1),
                                    (B, cfg.num_codebooks, P), 0,
                                    cfg.vocab_size)
    else:
        prompt = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0,
                                    cfg.vocab_size)

    caches = model.init_caches(B, P + G)
    prefill = jax.jit(lambda p, b, c: model.prefill(
        p, b, c, quantize=not args.no_compress))
    decode = jax.jit(lambda p, c, t, i: model.decode_step(p, c, t, i))

    t0 = time.time()
    logits, caches = prefill(params, {"tokens": prompt}, caches)
    print(f"prefill {P} tokens x{B}: {time.time() - t0:.2f}s "
          f"(uplink {'compressed' if not args.no_compress else 'raw'})")

    if model.pq is not None and not args.no_compress:
        bits = model.pq.message_bits(P, cfg.d_model)
        raw = 64 * cfg.d_model * P
        print(f"uplink per client: {bits / 8e3:.1f} kB vs raw {raw / 8e3:.1f} kB "
              f"({raw / bits:.0f}x)")

    generated = []
    t0 = time.time()
    for i in range(G):
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # greedy
        if cfg.num_codebooks > 1:
            nxt = jnp.moveaxis(nxt, -1, 1)  # (B, K, 1)
        generated.append(nxt)
        logits, caches = decode(params, caches, nxt, P + i)
    dt = time.time() - t0
    print(f"decoded {G} steps x{B} in {dt:.2f}s "
          f"({B * G / dt:.1f} tok/s on CPU, untuned)")
    first = generated[0]
    print("first generated ids:", jnp.squeeze(first)[..., ()] if first.ndim == 0
          else first.reshape(B, -1)[:, 0])


if __name__ == "__main__":
    main()
