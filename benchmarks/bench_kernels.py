"""Kernel micro-benchmarks: Pallas (interpret) vs pure-jnp reference, plus
the jnp assignment path used inside train steps. On CPU the interpret-mode
timing is NOT indicative of TPU performance — correctness + shape coverage
is the point; the jnp timings give the CPU substrate baseline.

Every run also writes ``BENCH_kernels.json`` at the repo root — one row per
kernel × backend (Lloyd update, scalarq quantize/pack, PQ encode, analytic
HBM-traffic models) — so the perf trajectory is tracked across PRs
(``benchmarks/run.py`` and the CI benchmark-smoke step both produce it)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_call, write_bench_json
from repro.core import kmeans as km
from repro.core.quantizer import PQConfig, quantize
from repro.kernels import ops, ref


def bench_encode_backends(rows):
    """End-to-end grouped-PQ encode, jnp vs fused pallas, on the paper's
    FEMNIST cut shape: B=8 examples x d=9216, q=1152 -> one group of
    N=8*1152=9216 subvector rows of dim 8.

    Wall-clock rows time the two *current* backends — both are single-pass
    encodes (the jnp scan body does assign+gather+subtract per chunk, which
    XLA fuses). Off-TPU the pallas row is interpret mode (correctness
    substrate); the wall-clock comparison is only meaningful on TPU.

    The traffic-model row is the structural claim of the registry refactor:
    the seed did the encode as separate sweeps (assign pass inside kmeans,
    centroid-gather write, then the correction VJP re-read X and z̃ to form
    the residual — 3 reads + 2 writes per element) where the fused encode
    does 1 read + 2 writes. That is analytic, not measured here.
    """
    B, d, q, L = 8, 9216, 1152, 16
    z = jax.random.normal(jax.random.PRNGKey(3), (B, d))
    n_rows, dsub = B * q, d // q
    for backend in ("jnp", "pallas"):
        cfg = PQConfig(num_subvectors=q, num_clusters=L, kmeans_iters=4,
                       backend=backend)
        us = time_call(jax.jit(lambda zz, c=cfg: quantize(zz, c).dequantized),
                       z, iters=1 if backend == "pallas" else 2, warmup=1)
        rows.append({
            "name": f"pq_encode_femnist_cut_{backend}_N{n_rows}_D{dsub}_L{L}",
            "us_per_call": us,
            "note": ("single-pass fused kernel (interpret off-TPU)"
                     if backend == "pallas" else "single-pass XLA-fused scan"),
        })
    elem = n_rows * dsub * 4
    rows.append({
        "name": "pq_encode_femnist_cut_traffic_model",
        "us_per_call": 0.0,
        "fused_encode_bytes": 3 * elem,       # 1 read + 2 writes
        "seed_separate_sweeps_bytes": 5 * elem,  # 3 reads + 2 writes
        "note": "analytic: fused encode vs the seed's assign/gather/"
                "residual-recompute structure",
    })


def bench_lloyd_update(rows, fast: bool = True):
    """The Lloyd-update hot loop: jnp scan (one-hot matmul + centroid
    re-read per chunk) vs the fused Pallas kernel (one HBM sweep).

    Off-TPU the pallas rows run in interpret mode — parity is the claim,
    not wall-clock. The traffic-model row is the structural argument: per
    iteration the fused kernel reads X once and writes the O(L·D)
    accumulators, where the scan path additionally materializes a (N, L)
    one-hot and re-reads the centroids for the deviation gather."""
    import numpy as np
    shapes = [(4096, 8, 16)] if fast else [(4096, 8, 16), (65536, 8, 32)]
    for n, d, l in shapes:
        x = jax.random.normal(jax.random.PRNGKey(0), (n, d))
        c = jax.random.normal(jax.random.PRNGKey(1), (l, d))
        w = jnp.ones((n,), jnp.float32)
        jnp_update = jax.jit(lambda a, cc: km._update_scan(
            km.get_backend("jnp").assign, a, w, cc, 4096))
        us_j = time_call(jnp_update, x, c)
        rows.append({"name": f"lloyd_update_jnp_n{n}_d{d}_L{l}",
                     "us_per_call": us_j,
                     "note": "scan: one-hot matmul + centroid re-read"})
        if n <= 16384:  # interpret mode is python-speed; keep it bounded
            us_p = time_call(lambda a, cc: ops.lloyd_update(
                a, cc, w), x, c, iters=1, warmup=1)
            ds_p, ct_p = ops.lloyd_update(x, c, w)
            ds_j, ct_j = jnp_update(x, c)
            err = float(np.abs(np.asarray(ds_p - ds_j)).max())
            rows.append({"name": f"lloyd_update_pallas_interpret_n{n}_d{d}_L{l}",
                         "us_per_call": us_p,
                         "max_err_vs_jnp": round(err, 7),
                         "note": "interpret-mode(correctness-only)"})
        f32 = 4
        rows.append({
            "name": f"lloyd_update_traffic_model_n{n}_d{d}_L{l}",
            "us_per_call": 0.0,
            "fused_bytes_per_iter": f32 * (n * d + n + l * d + l),
            "scan_bytes_per_iter": f32 * (2 * n * d + n + n * l + l * d + l),
            "note": "analytic: fused = 1 X read + O(L*D) accumulator writes;"
                    " scan adds a (N,L) one-hot + second centroid read",
        })


def bench_scalarq_kernels(rows):
    """The scalarq compressor's quantize + bit-pack hot loops, jnp vs the
    Pallas kernels (interpret off-TPU), next to the PQ encode rows."""
    import numpy as np
    n, d, bits = 2048, 64, 8
    x = jax.random.normal(jax.random.PRNGKey(5), (n, d))
    lo = jnp.min(x)
    scale = (jnp.max(x) - lo) / ((1 << bits) - 1)
    levels = (1 << bits) - 1

    def quant_jnp(a):
        codes = jnp.clip(jnp.round((a - lo) / scale), 0, levels) \
            .astype(jnp.int32)
        return codes, lo + codes.astype(jnp.float32) * scale

    us_j = time_call(jax.jit(quant_jnp), x)
    rows.append({"name": f"scalarq_quantize_jnp_n{n}_d{d}_b{bits}",
                 "us_per_call": us_j})
    us_p = time_call(lambda a: ops.scalar_quantize(a, lo, scale, bits),
                     x, iters=1, warmup=1)
    codes_j, _ = jax.jit(quant_jnp)(x)
    codes_p, _ = ops.scalar_quantize(x, lo, scale, bits)
    rows.append({"name": f"scalarq_quantize_pallas_interpret_n{n}_d{d}_b{bits}",
                 "us_per_call": us_p,
                 "codes_equal_jnp": bool((codes_j == codes_p).all()),
                 "note": "interpret-mode(correctness-only)"})

    flat = codes_j.reshape(-1)
    per_word = 32 // bits

    def pack_jnp(cc):
        mat = cc.reshape(-1, per_word).astype(jnp.uint32)
        weights = jnp.uint32(1) << (jnp.arange(per_word, dtype=jnp.uint32)
                                    * jnp.uint32(bits))
        return jnp.sum(mat * weights[None, :], axis=-1, dtype=jnp.uint32)

    us_pack_j = time_call(jax.jit(pack_jnp), flat)
    rows.append({"name": f"scalarq_pack_jnp_n{n * d}_b{bits}",
                 "us_per_call": us_pack_j})
    us_pack_p = time_call(lambda cc: ops.pack_codes(cc, bits),
                          flat, iters=1, warmup=1)
    words_j = jax.jit(pack_jnp)(flat)
    words_p = ops.pack_codes(flat, bits)
    rows.append({"name": f"scalarq_pack_pallas_interpret_n{n * d}_b{bits}",
                 "us_per_call": us_pack_p,
                 "words_equal_jnp": bool((words_j == words_p).all()),
                 "note": "interpret-mode(correctness-only)"})


def run(fast: bool = True):
    rows = []
    shapes = [(4096, 8, 16), (16384, 8, 16)] if fast else \
        [(4096, 8, 16), (16384, 8, 16), (65536, 8, 32), (16384, 64, 960)]
    for n, d, l in shapes:
        x = jax.random.normal(jax.random.PRNGKey(0), (n, d))
        c = jax.random.normal(jax.random.PRNGKey(1), (l, d))
        lmask = jnp.ones(l, jnp.float32)

        us_ref = time_call(jax.jit(
            lambda a, b: ref.kmeans_assign_ref(a, b, lmask)[0]), x, c)
        rows.append({"name": f"assign_jnp_n{n}_d{d}_L{l}",
                     "us_per_call": us_ref})
        if n <= 16384:  # interpret mode is python-speed; keep it bounded
            us_k = time_call(
                lambda a, b: ops.kmeans_assign(a, b)[0],
                x, c, iters=1, warmup=1)
            rows.append({"name": f"assign_pallas_interpret_n{n}_d{d}_L{l}",
                         "us_per_call": us_k,
                         "note": "interpret-mode(correctness-only)"})

        us_f = time_call(jax.jit(
            lambda a, b: km.kmeans(a, 16, 4).distortion), x, jnp.zeros(()),
            iters=2)
        rows.append({"name": f"kmeans_full_n{n}_d{d}", "us_per_call": us_f})

    bench_lloyd_update(rows, fast)
    bench_encode_backends(rows)
    bench_scalarq_kernels(rows)

    # flash-attention kernel parity check (interpret mode; TPU is the target)
    import math
    import numpy as np
    from repro.models.attention import row_block_attention
    B, S, H, Kv, hd = 1, 128, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, Kv, hd))
    v = jax.random.normal(ks[2], (B, S, Kv, hd))
    pos = jnp.arange(S)
    scale = 1.0 / math.sqrt(hd)
    ref_out = row_block_attention(q, k, v, pos, pos, window=None, q_chunk=S,
                                  scale=scale)
    out = ops.causal_attention(q, k, v, pos, pos, scale=scale)
    err = float(np.abs(np.asarray(out - ref_out)).max())
    rows.append({"name": f"flash_attention_S{S}_H{H}kv{Kv}",
                 "us_per_call": 0.0, "max_err_vs_rowblock": round(err, 7),
                 "note": "interpret-mode parity; O(S*d) HBM traffic on TPU"})
    # serialize before emit() strips the row keys
    write_bench_json(
        "kernels", rows,
        note="off-TPU pallas rows are interpret-mode (correctness, not "
             "speed); traffic_model rows are analytic bytes")
    return rows


def main(fast: bool = True):
    emit(run(fast), "kernels")


if __name__ == "__main__":
    main()
