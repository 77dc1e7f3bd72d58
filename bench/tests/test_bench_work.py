"""The FLOP and byte functions against hand counts at small shapes."""

import math
from types import SimpleNamespace

import pytest

from bench import kernels, tracing
from benchkit import load_builder, load_json


def test_femnist_model_flops_hand_count():
    b = load_builder("femnist_cnn")
    cfg = {"layers": {"conv1": [3, 3, 1, 2], "conv2": [3, 3, 2, 4],
                      "pool": [2, 2], "dense1": [576, 8], "dense2": [8, 3]}}
    mix = {"cohort": 2, "client_batch": 3}
    conv1 = 2 * 26 * 26 * 2 * 9 * 1       # outputs x MACs x 2
    conv2 = 2 * 24 * 24 * 4 * 9 * 2
    dense = 2 * 576 * 8 + 2 * 8 * 3
    want = 6 * (3 * (conv1 + conv2 + dense) - conv1)
    assert b.model_flops_per_update(cfg, mix) == want


def test_lm_model_flops_hand_count():
    b = load_builder("starcoder2_3b_d6")
    arch = {"d_model": 4, "d_ff": 8, "vocab_size": 10, "num_heads": 2,
            "head_dim": 2, "num_kv_heads": 1, "num_layers": 3}
    mix = {"cohort": 2, "client_batch": 1, "seq": 5}
    T = 10
    per_layer = 4 * 4 + 2 * 4 * 2 + 4 * 4 + 2 * 4 * 8   # q, k+v, o, mlp
    matmul = 2 * T * (3 * per_layer + 4 * 10)
    attn = 3 * 2 * 2 * 5 * 5 * 4   # layers x 2 matmuls x rows x S^2 x Q
    #                                (the causal half of 2·S²·Q each)
    assert b.model_flops_per_update({"arch": arch}, mix) == \
        3 * (matmul + attn)


def test_kernel_work_hand_count():
    pq = {"clients": 3, "points": 10, "dim": 8, "clusters": 2, "iters": 5}
    lu = kernels.lloyd_update(pq)
    assert lu["flops"] == 15 * 2 * 10 * 8 * 2
    assert lu["bytes"] == 15 * 4 * (10 * 8 + 2 * 2 * 8 + 2)
    enc = kernels.pq_encode(pq)
    assert enc["flops"] == 3 * 2 * 10 * 8 * 2
    assert enc["bytes"] == 3 * (4 * (3 * 80 + 16) + 4 * 10)


def test_least_time_names_its_bound():
    peaks = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}
    assert kernels.least_seconds({"flops": 50, "bytes": 100}, peaks) == \
        (10.0, "bytes")
    assert kernels.least_seconds({"flops": 5000, "bytes": 100}, peaks) == \
        (50.0, "flops")


def test_pq_work_of_both_configurations():
    f = load_builder("femnist_cnn").pq_work(
        load_json("configs/femnist_cnn.json"), load_json("traffic/cohort10.json"))
    assert f == {"clients": 10, "points": 1152 * 20, "dim": 8, "clusters": 2,
                 "iters": 5}
    s = load_builder("starcoder2_3b_d6").pq_work(
        load_json("configs/starcoder2_3b_d6.json"),
        load_json("traffic/seq2048.json"))
    assert s == {"clients": 4, "points": 384 * 2048, "dim": 8,
                 "clusters": 16, "iters": 4}
    m = load_builder("femnist_cnn").pq_work(
        load_json("configs/femnist_cnn.json"),
        load_json("traffic/mesh4_cohort40.json"))
    assert m == dict(f, clients=40)


def test_roofline_share_from_trace():
    MS = 1e6
    ops = [tracing.Event("pq_quantize_kernel.1", 0, 2 * MS)]
    pq = {"clients": 1, "points": 1000, "dim": 8, "clusters": 2, "iters": 1}
    ctx = SimpleNamespace(
        trace=tracing.Trace({"/device:TPU:0": ops}, []), window=(0, 10 * MS),
        updates=2, peaks={"flops_bf16": 1e12, "hbm_bytes_per_s": 1e9},
        builder=SimpleNamespace(pq_work=lambda c, m: pq), cfg={}, mix={})
    least = kernels.pq_encode(pq)["bytes"] / 1e9
    assert kernels.roofline_share(ctx, "pq_encode") == \
        pytest.approx(100 * 2 * least / 2e-3)
    assert kernels.roofline_share(ctx, "lloyd_update") is None
    assert math.isfinite(least)
    # the same work split over two chips: their times add up
    half = [tracing.Event("pq_quantize_kernel.1", 0, 1 * MS)]
    ctx.trace = tracing.Trace({"/device:TPU:0": half, "/device:TPU:1": half},
                              [])
    assert kernels.roofline_share(ctx, "pq_encode") == \
        pytest.approx(100 * 2 * least / 2e-3)
