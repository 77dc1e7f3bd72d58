"""The Moonlight-16B-A3B cell at small sizes on the CPU: the builder's
layout is the program's, its FLOP and byte counts match hand counts, the
program's whole split step (loss and every leaf's gradient) matches the
plain reference, the harness reads a sound run as correct and the control
and a fault as not, and the grouped-matmul readers read a trace."""

import functools
import json
import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchkit
from bench import expert_kernels, tracing
from benchkit import BENCH, load_builder, load_json, make_root

CONFIG = "moonlight_16b_a3b_ep8_d6"
MIX = "seq8192"

# the published keys and the program's arch at a CPU test's size: 3 layers
# (the dense one and two expert layers), 4 of 16 experts held, top-4, one
# shared expert, values 16 wide against queries and keys of 24; float32 so
# that the program and the reference agree to rounding
TINY_PUBLISHED = dict(
    hidden_size=64, intermediate_size=128, kv_lora_rank=32,
    moe_intermediate_size=32, n_routed_experts=16, n_shared_experts=1,
    num_attention_heads=4, num_key_value_heads=4, num_experts_per_tok=4,
    num_hidden_layers=3, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, vocab_size=256, experts_held=4)
TINY_ARCH = dict(
    num_layers=3, d_model=64, vocab_size=256, num_heads=4, num_kv_heads=4,
    head_dim=24, kv_lora_rank=32, qk_rope_dim=8, v_head_dim=16, d_ff=128,
    num_experts=16, experts_per_token=4, moe_d_ff=32, experts_held=4,
    expert_offset=4, num_shared_experts=1, attn_q_chunk=32,
    dtype="float32", param_dtype="float32")
TINY = dict(TINY_PUBLISHED, arch=TINY_ARCH,
            pq={"num_subvectors": 8, "num_clusters": 4, "kmeans_iters": 2})
TINY_MIX = {"num_clients": 8, "cohort": 2, "seq": 64, "batches_per_client": 4}


@pytest.fixture
def tiny(monkeypatch):
    monkeypatch.setitem(benchkit.TINY_CFG, CONFIG, TINY)
    monkeypatch.setitem(benchkit.TINY_MIX, MIX, TINY_MIX)
    monkeypatch.setitem(benchkit.MIX, CONFIG, MIX)
    cfg = benchkit._merge(load_json(f"configs/{CONFIG}.json"), TINY)
    mix = benchkit._merge(load_json(f"traffic/{MIX}.json"), TINY_MIX)
    return cfg, mix


def test_published_keys_and_arch_agree():
    b = load_builder(CONFIG)
    cfg = load_json(f"configs/{CONFIG}.json")
    a = b.arch(cfg)
    assert (a["num_experts"], a["experts_held"], a["experts_per_token"]) == \
        (64, 8, 6)
    assert cfg["published"]["vocab_size"] == 8 * cfg["vocab_size"]
    bad = json.loads(json.dumps(cfg))
    bad["arch"]["moe_d_ff"] = 1024
    with pytest.raises(ValueError, match="moe_d_ff"):
        b.arch(bad)


def test_layout_is_the_programs(tiny):
    from bench import params as P
    from repro.configs.base import ArchConfig
    from repro.models.transformer import TransformerLM
    b = load_builder(CONFIG)
    cfg, _ = tiny
    frozen = P.freeze(b.param_layout(cfg))
    key = jax.random.PRNGKey(0)
    model = TransformerLM(ArchConfig(**b.arch(cfg)))
    P.check_layout(jax.eval_shape(P.make, frozen, key),
                   jax.eval_shape(model.init, key))
    # the full cell: 669 M parameters, as the configuration file states
    full = P.freeze(b.param_layout(load_json(f"configs/{CONFIG}.json")))
    total = sum(math.prod(spec[0]) for _, spec in full)
    assert round(total / 1e6) == 669


def test_model_flops_and_moe_work_hand_count(tiny):
    b = load_builder(CONFIG)
    cfg, mix = tiny
    T = 2 * 64
    attn = 64 * 4 * 24 + 64 * (32 + 8) + 32 * 4 * (16 + 16) + 4 * 16 * 64
    dense = 3 * 64 * 128
    moe = 64 * 16 + 3 * 64 * 32 * 1 + 3 * 64 * 32 * (4 * 4 / 16)
    per_token = 3 * attn + dense + 2 * moe + 64 * 256
    scores = 3 * 2 * 64 * 64 * 4 * (24 + 16)    # layers x rows x S² x H x
    #                                             (qk + v): the causal half
    assert b.model_flops_per_update(cfg, mix) == \
        pytest.approx(3 * (2 * T * per_token + scores))
    rows = T * 4 * 4 / 16
    work = b.moe_work(cfg, mix)
    assert work["flops"] == pytest.approx(2 * 9 * 2 * rows * 64 * 32)
    assert work["bytes"] == pytest.approx(
        2 * 9 * 2 * (rows * (64 + 32) + 4 * 64 * 32))
    assert b.pq_work(cfg, mix) == {"clients": 2, "points": 8 * 64, "dim": 8,
                                   "clusters": 4, "iters": 2}


def test_split_step_loss_and_gradients_match_the_reference(tiny,
                                                          monkeypatch):
    """The program's loss and every leaf's gradient of one split step — the
    grouped-matmul kernels in interpret mode, the PQ cut with its corrected
    gradient — against the reference's, from the same weights."""
    from bench import params as P
    from bench.harness import load_module
    from bench.reference import common as C
    from repro.configs.base import ArchConfig
    from repro.core.quantizer import PQConfig
    from repro.kernels import ops
    from repro.models import moe
    from repro.models.transformer import TransformerLM
    monkeypatch.setattr(moe, "grouped_matmul", functools.partial(
        ops.grouped_matmul, backend="pallas"))
    b = load_builder(CONFIG)
    ref = load_module(BENCH / "reference" / f"{CONFIG}.py", "ref_moonlight")
    cfg, _ = tiny
    a = b.arch(cfg)
    key = jax.random.PRNGKey(11)
    params = P.make(P.freeze(b.param_layout(cfg)), key)
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (2, 64), 0, 256)
    labels = jnp.concatenate([tokens[:, 1:], jnp.full((2, 1), -1)], axis=1)
    model = TransformerLM(ArchConfig(**a),
                          pq=PQConfig(**cfg["pq"]), lam=cfg["lam"])
    loss, grads = jax.value_and_grad(
        lambda p: model.loss(p, {"tokens": tokens, "labels": labels})[0])(
            params)

    z, client_vjp = jax.vjp(
        lambda cp: ref._client(cp, tokens, a, "highest"), params["client"])
    zt, resid = C.quantize_clients(z.reshape(128, -1), 64, cfg["pq"])
    want, (g_server, g_zt) = jax.value_and_grad(ref._server_loss, (0, 1))(
        params["server"], zt.reshape(z.shape), labels, a, "highest")
    (g_client,) = client_vjp(g_zt + cfg["lam"] * resid.reshape(z.shape))
    expect = {"client": g_client, "server": g_server}

    assert float(loss) == pytest.approx(float(want), rel=1e-4)
    got, exp = P.leaf_paths(grads), jax.tree.leaves(expect)
    for path, g, e in zip(got, jax.tree.leaves(grads), exp):
        if path.endswith("router_bias"):     # held fixed: no gradient
            assert float(jnp.abs(g).max()) == 0.0 == float(jnp.abs(e).max())
            continue
        err = float(jnp.linalg.norm(g - e) / jnp.linalg.norm(e))
        assert err < 2e-3, (path, err)


def test_sound_run_is_correct_and_control_and_fault_are_not(
        tmp_path, cpu_jax, tiny):
    import time
    from bench import calibrate, harness
    root, wl = make_root(tmp_path, CONFIG)
    res = harness.run(root, wl, 7, 0.5, False, t_start=time.perf_counter(),
                      require_tpu=False)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    cell = harness.locate(root, wl)
    line = calibrate.readings(root, wl, 9)
    limits = cell.limits["limits"]
    assert all(line["program"][k] <= limits[k] for k in limits), line
    assert any(line["control"][k] > limits[k] for k in limits), line
    assert any(line["half_batch"][k] > limits[k] for k in limits), line


def test_expert_kernel_readers_from_trace():
    MS = 1e6
    ops = [tracing.Event("moe_gmm_kernel.102", 0, 3 * MS),
           tracing.Event("transpose_jvp_jit_moe_tgmm_kernel___.2", 3 * MS,
                         4 * MS),
           tracing.Event("lloyd_update_kernel.6", 4 * MS, 9 * MS)]
    work = {"flops": 2e9, "bytes": 1e6}
    ctx = SimpleNamespace(
        trace=tracing.Trace({"/device:TPU:0": ops}, []), window=(0, 10 * MS),
        updates=2, peaks={"flops_bf16": 1e12, "hbm_bytes_per_s": 1e9},
        builder=SimpleNamespace(moe_work=lambda c, m: work), cfg={}, mix={})
    from bench.harness import load_module
    read_ms = load_module(BENCH / "metrics" / "moe_experts_ms.py", "r1").read
    read_rf = load_module(BENCH / "metrics" / "moe_experts_roofline.py",
                          "r2").read
    assert read_ms(ctx) == pytest.approx(2.0)          # 4 ms over 2 updates
    assert read_rf(ctx) == pytest.approx(100 * 2 * 2e-3 / 4e-3)
    # a program without the kernels, or a configuration without the work
    ctx.trace = tracing.Trace({"/device:TPU:0": ops[2:]}, [])
    assert read_ms(ctx) is None and read_rf(ctx) is None
    ctx.trace = tracing.Trace({"/device:TPU:0": ops}, [])
    ctx.builder = SimpleNamespace()
    assert expert_kernels.roofline_share(ctx) is None
    assert np.isfinite(read_ms(ctx))
