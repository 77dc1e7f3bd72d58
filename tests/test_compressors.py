"""Compressor-stack tests: wire round-trips for every registered codec,
error-feedback semantics, VJP equivalence, and per-contribution staleness
weighting through the federated runtime."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compressors as C
from repro.core.quantizer import PQConfig
from repro.federated import wire

PQ = PQConfig(num_subvectors=8, num_clusters=4, kmeans_iters=2)


def _z(shape=(12, 64), seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape)


def _all_specs():
    return ["none", "pq", "topk(k=0.1)", "scalarq(bits=8)",
            "chain:topk(k=0.25)+scalarq(bits=4)"]


# ---------------------------------------------------------------------------
# wire round-trips: bit-exact for every registered compressor
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", _all_specs())
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_wire_roundtrip_bit_exact(spec, backend):
    """encode -> decode -> re-encode is byte-identical, and the decoded
    reconstruction matches the in-jit reconstruction (f32 wire dtype) for
    every codec, on both the jnp and pallas(-interpret) backends."""
    if spec == "pq":
        comp_obj = C.PQCompressor(
            cfg=PQConfig(num_subvectors=8, num_clusters=4, kmeans_iters=2,
                         backend=backend))
    elif spec.startswith("scalarq"):
        comp_obj = C.ScalarQuantCompressor(bits=8, backend=backend)
    else:
        comp_obj = C.make_compressor(spec, pq=PQ)
    z = _z()
    comp = comp_obj.compress(z)
    buf = comp_obj.wire_payload(comp, value_dtype="float32")
    dp = wire.decode_payload(buf)
    assert wire.encode_decoded(dp) == buf          # idempotent re-encode
    rec = wire.reconstruct(dp)
    assert rec.shape == (12, 64)
    np.testing.assert_allclose(rec, np.asarray(comp.recon), atol=1e-6)
    # codes/indices survive the wire exactly (the lossy steps are value
    # dtype casts only, and f32 was used above)
    if dp.kind == "sparse":
        sp = comp.payload if isinstance(comp.payload, C.SparsePayload) \
            else comp.payload[0]
        np.testing.assert_array_equal(dp.arrays["indices"],
                                      np.asarray(sp.indices))
    if dp.kind == "scalar":
        np.testing.assert_array_equal(
            dp.arrays["codes"].reshape(-1),
            np.asarray(comp.payload.codes).reshape(-1))


@pytest.mark.parametrize("spec", _all_specs())
def test_measured_bytes_track_analytic(spec):
    """len(wire_payload)*8 is within the per-stage header + CRC-trailer
    overhead of analytic_bits at the wire width."""
    c = C.make_compressor(spec, pq=PQ)
    z = _z()
    buf = c.wire_payload(c.compress(z), value_dtype="float32")
    analytic = c.analytic_bits(12, 64, phi_bits=32)
    stages = len(c.stages) if isinstance(c, C.ChainCompressor) else 1
    overhead = len(buf) * 8 - analytic
    frame = (wire.HEADER_BYTES + wire.CRC_BYTES) * 8 + 7
    assert 0 <= overhead <= stages * frame, (spec, overhead)


def test_multi_carrier_chain_roundtrip():
    """Chains with more than one carrier stage encode each stage against
    ITS OWN input geometry (regression: inner indices once used the outer
    tensor's n*d and the payload could not be decoded)."""
    c = C.make_compressor("chain:topk(k=0.5)+topk(k=0.5)")
    z = _z((8, 48), seed=7)
    comp = c.compress(z)
    buf = c.wire_payload(comp, value_dtype="float32")
    dp = wire.decode_payload(buf)
    assert dp.kind == "sparse" and dp.inner is not None
    assert dp.inner.kind == "sparse"
    assert dp.inner.n * dp.inner.d == c.stages[0].k_count(z.size)
    np.testing.assert_allclose(wire.reconstruct(dp),
                               np.asarray(comp.recon), atol=1e-6)
    assert wire.encode_decoded(dp) == buf
    # analytic accounting agrees to within the per-stage frame overhead
    overhead = len(buf) * 8 - c.analytic_bits(8, 48, 32)
    assert 0 <= overhead <= 2 * ((wire.HEADER_BYTES + wire.CRC_BYTES) * 8 + 7)


def test_chain_hits_acceptance_ratio():
    """The acceptance codec cuts the FEMNIST-cut gradient >= 8x, measured."""
    c = C.make_compressor("chain:topk(k=0.1)+scalarq(bits=8)")
    g = _z((8, 9216), seed=3)   # client_batch x cut_dim
    buf = c.wire_payload(c.compress(g))
    dense = g.size * 4
    assert dense / len(buf) >= 8.0
    # analytic model agrees
    assert 32 * g.size / c.analytic_bits(8, 9216, 32) >= 8.0


# ---------------------------------------------------------------------------
# compressor math
# ---------------------------------------------------------------------------

def test_topk_keeps_largest_magnitudes():
    c = C.TopKCompressor(k=0.25)
    z = jnp.arange(1.0, 17.0).reshape(4, 4) * jnp.asarray([1, -1] * 8
                                                          ).reshape(4, 4)
    comp = c.compress(z)
    kept = np.flatnonzero(np.asarray(comp.recon).reshape(-1))
    assert set(kept) == {12, 13, 14, 15}        # the four largest |z|
    np.testing.assert_array_equal(
        np.asarray(comp.recon).reshape(-1)[kept],
        np.asarray(z).reshape(-1)[kept])        # survivors pass unchanged
    np.testing.assert_allclose(np.asarray(comp.recon + comp.residual),
                               np.asarray(z), rtol=1e-6)


def test_scalarq_quantization_error_bounded():
    c = C.ScalarQuantCompressor(bits=8, backend="jnp")
    z = _z((16, 32), seed=1)
    comp = c.compress(z)
    scale = float(np.asarray(comp.payload.scale))
    # nearest rounding: error <= scale/2 everywhere
    assert float(jnp.abs(comp.residual).max()) <= scale / 2 + 1e-6


def test_scalarq_stochastic_rounding_unbiased():
    """With stochastic rounding, E[recon] -> z (mean over many keys)."""
    c = C.ScalarQuantCompressor(bits=4, backend="jnp")
    z = _z((4, 16), seed=2)
    recs = [c.compress(z, key=jax.random.PRNGKey(i)).recon
            for i in range(200)]
    mean = np.mean([np.asarray(r) for r in recs], axis=0)
    scale = float(np.asarray(c.compress(z).payload.scale))
    # the empirical mean lands far inside one quantization step of z
    assert np.abs(mean - np.asarray(z)).max() < 0.2 * scale


def test_scalarq_jnp_pallas_parity():
    z = _z((16, 64), seed=4)
    a = C.ScalarQuantCompressor(bits=8, backend="jnp").compress(z)
    b = C.ScalarQuantCompressor(bits=8, backend="pallas").compress(z)
    np.testing.assert_array_equal(np.asarray(a.payload.codes),
                                  np.asarray(b.payload.codes))
    np.testing.assert_allclose(np.asarray(a.recon), np.asarray(b.recon),
                               atol=1e-6)


def test_device_pack_matches_host_stream():
    """The Pallas pack kernel writes the identical LSB-first byte stream
    the host wire codec writes (32 % bits == 0 widths)."""
    from repro.federated.wire import _pack_codes
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    for bits in (2, 4, 8, 16):
        codes = rng.integers(0, 1 << bits, size=999).astype(np.int64)
        host = _pack_codes(codes.astype(np.uint32), bits)
        dev = np.asarray(ops.pack_codes(jnp.asarray(codes, jnp.int32), bits))
        assert dev.tobytes()[:len(host)] == host
        back = np.asarray(ops.unpack_codes(jnp.asarray(dev), codes.size,
                                           bits))
        np.testing.assert_array_equal(back, codes)


def test_spec_parser_and_registry():
    assert isinstance(C.make_compressor("none"), C.NoneCompressor)
    c = C.make_compressor("chain:topk(k=0.5)+scalarq(bits=4, backend=jnp)")
    assert isinstance(c, C.ChainCompressor)
    assert c.stages[0].k == 0.5 and c.stages[1].bits == 4
    assert C.make_compressor(c) is c
    assert C.make_compressor(None) is None
    with pytest.raises(ValueError):
        C.make_compressor("nosuch(k=1)")
    with pytest.raises(ValueError):
        C.make_compressor("pq")             # needs a PQConfig
    with pytest.raises(ValueError):
        C.make_compressor("chain:scalarq(bits=8)+topk(k=0.1)")  # terminal mid-chain
    with pytest.raises(ValueError):
        C.make_compressor("topk(k=1.5)")


# ---------------------------------------------------------------------------
# error feedback
# ---------------------------------------------------------------------------

def test_error_feedback_accumulates_and_flushes():
    """EF invariants: (i) recon + memory' == z + memory exactly (nothing is
    lost, only delayed); (ii) over repeated rounds on a constant signal the
    cumulative transmitted mass approaches the cumulative signal."""
    ef = C.ErrorFeedback(C.TopKCompressor(k=0.125))
    z = _z((4, 16), seed=5)
    mem = ef.init_memory(z)
    sent = jnp.zeros_like(z)
    for _ in range(12):
        comp, new_mem = ef.step(z, mem)
        np.testing.assert_allclose(np.asarray(comp.recon + new_mem),
                                   np.asarray(z + mem), rtol=1e-5,
                                   atol=1e-6)
        mem = new_mem
        sent = sent + comp.recon
    # telescoping: cumulative transmitted + residual memory == cumulative
    # signal, exactly — compression only DELAYS mass, never loses it
    np.testing.assert_allclose(np.asarray(sent + mem), np.asarray(12.0 * z),
                               rtol=1e-4, atol=1e-5)
    # and the memory stays bounded: far below one round's worth per 12
    assert float(jnp.abs(mem).max()) < 12 * float(jnp.abs(z).max())


def test_error_feedback_identity_for_none():
    ef = C.ErrorFeedback(C.NoneCompressor())
    z = _z((2, 8))
    comp, mem = ef.step(z, ef.init_memory(z))
    np.testing.assert_array_equal(np.asarray(comp.recon), np.asarray(z))
    np.testing.assert_array_equal(np.asarray(mem), np.zeros_like(z))


# ---------------------------------------------------------------------------
# VJP hooks
# ---------------------------------------------------------------------------

def test_downlink_none_is_bitwise_identity():
    """downlink_compressor="none" reproduces the uncompressed backward pass
    bit for bit — the acceptance-criteria equivalence."""
    cn = C.NoneCompressor()
    z = _z((6, 32))

    def f_hooked(x):
        return jnp.sum(jnp.sin(C.downlink(x, None, None, cn)) ** 2)

    def f_plain(x):
        return jnp.sum(jnp.sin(x) ** 2)

    g_h = jax.grad(f_hooked)(z)
    g_p = jax.grad(f_plain)(z)
    np.testing.assert_array_equal(np.asarray(g_h), np.asarray(g_p))


def test_downlink_compresses_cotangent_only():
    """Forward values are untouched; the backward cotangent is sparsified."""
    c = C.TopKCompressor(k=0.1)
    z = _z((4, 64))
    out, vjp = jax.vjp(lambda x: C.downlink(x, None, None, c), z)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(z))
    g = jax.random.normal(jax.random.PRNGKey(1), z.shape)
    (gz,) = vjp(g)
    nz = int(jnp.sum(gz != 0))
    assert nz == c.k_count(z.size)
    # surviving entries pass through unchanged
    mask = np.asarray(gz != 0)
    np.testing.assert_allclose(np.asarray(gz)[mask], np.asarray(g)[mask],
                               rtol=1e-6)


def test_uplink_hook_matches_pq_path():
    """The uplink hook over PQCompressor is the grouped PQ itself: forward
    == ``quantize(z).dequantized``, distortion == the quantizer's own, and
    the cotangent == g + λ·residual (eq. 5, the fused encode's residual)."""
    from repro.core.quantizer import quantize
    z = _z((10, 64), seed=6)
    g = _z((10, 64), seed=7)
    lam = 0.3
    qb = quantize(z, PQ)
    (recon, dist), vjp = jax.vjp(
        lambda x: C.uplink(x, lam, C.CutState(),
                           C.PQCompressor(cfg=PQ))[:2], z)
    np.testing.assert_array_equal(np.asarray(recon),
                                  np.asarray(qb.dequantized))
    np.testing.assert_array_equal(np.asarray(dist),
                                  np.asarray(qb.distortion))
    (gz,) = vjp((g, jnp.zeros(())))
    np.testing.assert_allclose(np.asarray(gz),
                               np.asarray(g + lam * qb.residual),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("case", ["femnist_split", "femnist_one_client",
                                  "lm_rows"])
def test_cut_is_the_hooks_per_client(case):
    """The models' shared cut (``apply_cut``) gives each client what the two
    hooks give on that client alone: forward, cotangent (with the step key
    split per client for a stochastic downlink) and stats. FEMNIST with
    client_batch 4 splits 8 examples into 2 clients and keeps 6 as one
    client; the LM makes each sequence a client."""
    from repro.models import paper_models
    lam, key = 0.3, jax.random.PRNGKey(5)
    down = C.make_compressor("scalarq(bits=4)")
    if case == "lm_rows":
        from repro.configs.base import get_arch
        from repro.models.transformer import TransformerLM
        cfg = get_arch("llama3_8b", smoke=True)
        pq = PQConfig(num_subvectors=cfg.d_model // 8, num_clusters=4,
                      kmeans_iters=2)
        model = TransformerLM(cfg, pq=pq, lam=lam, downlink_compressor=down)
        x = _z((2, 16, cfg.d_model), seed=11)
        clients = [x[0], x[1]]

        def cut(v):
            return model._cut(v, quantize=True, key=key)
    else:
        model = paper_models.FemnistCNN(pq=PQ, lam=lam, client_batch=4,
                                        downlink_compressor=down)
        x = _z((8 if case == "femnist_split" else 6, 64), seed=11)
        clients = [x[:4], x[4:]] if case == "femnist_split" else [x]

        def cut(v):
            return paper_models._cut(model, v, lam, key, None)

    g = _z(x.shape, seed=12)
    zt, vjp, stats = jax.vjp(cut, x, has_aux=True)
    (gx,) = vjp(g)
    up = C.PQCompressor(model.pq)
    keys = jax.random.split(key, len(clients))
    dists, outs, grads = [], [], []
    for zi, ki, gi in zip(clients, keys, np.split(np.asarray(g),
                                                  len(clients))):
        def hooks(v):
            r, dist, _ = C.uplink(v, lam, C.CutState(), up)
            return C.downlink(r, ki, None, down), dist

        (out, dist), hvjp = jax.vjp(hooks, zi)
        dists.append(float(dist))
        outs.append(np.asarray(out))
        grads.append(np.asarray(hvjp((gi.reshape(zi.shape),
                                      jnp.zeros(())))[0]))
    np.testing.assert_allclose(np.asarray(zt).reshape(-1),
                               np.concatenate(outs, None), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(gx).reshape(-1),
                               np.concatenate(grads, None), rtol=1e-5,
                               atol=1e-6)
    n = clients[0].size // x.shape[-1]
    np.testing.assert_allclose(float(stats["pq_distortion"]),
                               np.mean(dists), rtol=1e-5)
    assert stats["pq_compression_ratio"] == \
        model.pq.compression_ratio(n, x.shape[-1])
    assert stats["downlink_message_bits"] == len(clients) * \
        down.analytic_bits(n, x.shape[-1], 32)


def test_model_downlink_none_bitwise_grads():
    """FemnistCNN: grads with downlink "none" == grads with no downlink."""
    from repro.models.paper_models import FemnistCNN
    pq = PQConfig(num_subvectors=288, num_clusters=4, kmeans_iters=2)
    m0 = FemnistCNN(pq=pq, lam=1e-4)
    m1 = FemnistCNN(pq=pq, lam=1e-4,
                    downlink_compressor=C.make_compressor("none"))
    params = m0.init(jax.random.PRNGKey(0))
    batch = {"image": jax.random.normal(jax.random.PRNGKey(1),
                                        (8, 28, 28, 1)),
             "label": jnp.zeros((8,), jnp.int32)}
    g0 = jax.grad(lambda p: m0.loss(p, batch)[0])(params)
    g1 = jax.grad(lambda p: m1.loss(p, batch)[0])(params)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_model_downlink_chain_touches_client_grads_only():
    """A lossy downlink codec changes CLIENT grads (they live below the
    cut) but leaves server grads bit-identical (they live above it)."""
    from repro.models.paper_models import FemnistCNN
    pq = PQConfig(num_subvectors=288, num_clusters=4, kmeans_iters=2)
    dl = C.make_compressor("chain:topk(k=0.1)+scalarq(bits=8)")
    m0 = FemnistCNN(pq=pq, lam=1e-4)
    m1 = FemnistCNN(pq=pq, lam=1e-4, downlink_compressor=dl)
    params = m0.init(jax.random.PRNGKey(0))
    batch = {"image": jax.random.normal(jax.random.PRNGKey(1),
                                        (8, 28, 28, 1)),
             "label": jnp.zeros((8,), jnp.int32)}
    g0 = jax.grad(lambda p: m0.loss(p, batch)[0])(params)
    g1 = jax.grad(lambda p: m1.loss(p, batch)[0])(params)
    for a, b in zip(jax.tree.leaves(g0["server"]),
                    jax.tree.leaves(g1["server"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    diffs = [float(jnp.abs(a - b).max()) for a, b in
             zip(jax.tree.leaves(g0["client"]),
                 jax.tree.leaves(g1["client"]))]
    assert max(diffs) > 0
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree.leaves(g1))


def test_transformer_downlink_spec_via_arch_config():
    """ArchConfig.downlink_compressor reaches the LM's cut layer."""
    import dataclasses as dc
    from repro.configs.base import get_arch
    from repro.data.synthetic import make_lm_batch
    from repro.launch.specs import make_model
    cfg = dc.replace(get_arch("llama3_8b", smoke=True),
                     downlink_compressor="chain:topk(k=0.1)+scalarq(bits=8)")
    model = make_model(cfg)
    assert model.downlink_compressor is not None
    params = model.init(jax.random.PRNGKey(0))
    batch = make_lm_batch(jax.random.PRNGKey(1), 2, 32, cfg.vocab_size)
    (loss, metrics), g = jax.value_and_grad(
        lambda p: model.loss(p, batch), has_aux=True)(params)
    assert np.isfinite(float(loss))
    assert metrics["downlink_message_bits"] > 0
    assert all(np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(g))


# ---------------------------------------------------------------------------
# runtime integration: measured downlink + per-contribution staleness
# ---------------------------------------------------------------------------

def _trainer(**kw):
    from repro.data.synthetic import make_federated_image_data
    from repro.federated import FederatedTrainer
    from repro.models.paper_models import FemnistCNN
    from repro.optim import sgd
    data = make_federated_image_data(num_clients=8, seed=0)
    pq = PQConfig(num_subvectors=288, num_clusters=4, kmeans_iters=2)
    model = FemnistCNN(pq=pq, lam=1e-4)
    return FederatedTrainer(model, sgd(0.03), data, cohort=4, client_batch=8,
                            **kw)


def test_trainer_measures_compressed_downlink():
    tr = _trainer(downlink_compressor="chain:topk(k=0.1)+scalarq(bits=8)")
    state = tr.init_state(jax.random.PRNGKey(0))
    up, down = tr.measure_round_bytes(state, jax.random.PRNGKey(1))
    dense = tr.measure_dense_bytes(state, jax.random.PRNGKey(1))
    assert dense / down >= 8.0          # the acceptance reduction, measured
    assert tr.model.downlink_compressor is not None   # installed in the VJP


def test_trainer_downlink_none_bitwise_trajectory():
    key = jax.random.PRNGKey(0)
    s1, _ = _trainer(downlink_compressor="none").run(3, key)
    s2, _ = _trainer().run(3, key)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_trainer_downlink_chain_still_trains():
    tr = _trainer(downlink_compressor="chain:topk(k=0.3)+scalarq(bits=8)")
    _, hist = tr.run(6, jax.random.PRNGKey(0))
    losses = [h["loss"] for h in hist]
    assert np.isfinite(losses).all()
    assert min(losses[1:]) < losses[0]
    assert tr.last_trace.meta["downlink_compressor"] == \
        "chain:topk(k=0.3)+scalarq(bits=8)"
    rec = tr.last_trace.records[0]
    assert rec.downlink_bytes < rec.uplink_bytes * 100   # sanity: measured


def test_per_contribution_staleness_weighting():
    """AsyncBuffer: the weighted step discounts each contribution by its
    own staleness — verified against a hand-rolled per-client computation."""
    from repro.core.fedlite import TrainState, make_weighted_step
    from repro.models.paper_models import FemnistCNN
    from repro.optim import sgd
    pq = PQConfig(num_subvectors=288, num_clusters=4, kmeans_iters=2)
    model = FemnistCNN(pq=pq, lam=1e-4)
    opt = sgd(0.1)
    params = model.init(jax.random.PRNGKey(0))
    state = TrainState.create(params, opt)
    batches = {
        "image": jax.random.normal(jax.random.PRNGKey(1), (2, 4, 28, 28, 1)),
        "label": jnp.zeros((2, 4), jnp.int32),
    }
    weights = jnp.asarray([1.0, 0.25])
    # donate=False: the manual check below reuses the pre-step params
    step = make_weighted_step(model, opt, donate=False)
    new_state, metrics = step(state, batches, weights)

    # hand-rolled: per-client grads, FedBuff mean of w_i * g_i, one SGD step
    def one(b):
        return jax.grad(lambda p: model.loss(p, b)[0])(params)

    g0 = one({"image": batches["image"][0], "label": batches["label"][0]})
    g1 = one({"image": batches["image"][1], "label": batches["label"][1]})
    expect = jax.tree.map(lambda a, b: (1.0 * a + 0.25 * b) / 2.0, g0, g1)
    manual = jax.tree.map(lambda p, g: p - 0.1 * g, params, expect)
    for a, b in zip(jax.tree.leaves(new_state.params),
                    jax.tree.leaves(manual)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_async_run_uses_per_contribution_weights():
    from repro.federated import AsyncBuffer
    tr = _trainer(policy=AsyncBuffer(2))
    _, hist = tr.run(4, jax.random.PRNGKey(0))
    assert all(np.isfinite(h["loss"]) for h in hist)
    # at least one flush mixed stalenesses -> the weighted path ran
    stale = [r.staleness for r in tr.last_trace]
    assert any(len(set(s)) >= 1 for s in stale)


# ---------------------------------------------------------------------------
# cross-round cut-layer state in the trainer (PR 4)
# ---------------------------------------------------------------------------

def test_trainer_warm_start_carries_codebooks_across_rounds():
    tr = _trainer(warm_start=True)
    _, hist = tr.run(3, jax.random.PRNGKey(0))
    assert all(np.isfinite(h["loss"]) for h in hist)
    assert tr._global_q is not None
    assert int(tr._global_q.rounds) == 3          # one warm lineage
    assert tr.last_trace.meta["warm_start"] is True
    # history metrics stay scalar: the cut state was popped before logging
    assert all("cut_state" not in h for h in hist)


def test_trainer_error_feedback_carries_memory_across_rounds():
    tr = _trainer(error_feedback=True)
    _, hist = tr.run(3, jax.random.PRNGKey(0))
    assert all(np.isfinite(h["loss"]) for h in hist)
    assert len(tr._ef_memory) > 0                 # per-client slots populated
    mem = next(iter(tr._ef_memory.values()))
    assert mem.shape == (8, 9216)                 # client_batch x cut dim
    assert float(jnp.abs(mem).max()) > 0.0        # PQ is lossy: error nonzero


def test_trainer_async_warm_start_per_client_slots():
    from repro.federated import AsyncBuffer
    tr = _trainer(warm_start=True, error_feedback=True,
                  policy=AsyncBuffer(2))
    _, hist = tr.run(4, jax.random.PRNGKey(0))
    assert all(np.isfinite(h["loss"]) for h in hist)
    assert len(tr._client_q) > 0                  # per-client codebooks
    q = next(iter(tr._client_q.values()))
    assert q.codebooks.shape == (1, 4, 32)        # (R, L, d/q) for q=288


def test_trainer_codebook_delta_measured_bytes():
    tr = _trainer(codebook_delta_bits=8)
    state = tr.init_state(jax.random.PRNGKey(0))
    up, _ = tr.measure_round_bytes(state, jax.random.PRNGKey(1))
    meta = tr.last_codebook_meta
    assert up == meta["uplink_bytes_delta_codebook"]
    assert meta["codebook_bytes_delta"] < meta["codebook_bytes_full"]
    assert meta["codebook_bytes_reduction"] > 1.0


def test_trainer_rejects_bad_cut_state_configs():
    with pytest.raises(ValueError, match="pq uplink"):
        _trainer(warm_start=True, uplink_compressor="none", quantize=False)
    with pytest.raises(ValueError, match="quantize"):
        _trainer(error_feedback=True, quantize=False,
                 uplink_compressor="none")
    with pytest.raises(ValueError, match="codebook_delta_bits"):
        _trainer(codebook_delta_bits=99)


def test_stochastic_downlink_key_changes_gradients_not_keyless_path():
    """A step_key makes the scalarq downlink round stochastically (grads
    differ across keys); the keyless step stays bitwise-identical to the
    historical deterministic path."""
    from repro.core.fedlite import TrainState, make_train_step
    from repro.models.paper_models import FemnistCNN
    from repro.optim import sgd
    pq = PQConfig(num_subvectors=288, num_clusters=4, kmeans_iters=2)
    model = FemnistCNN(pq=pq, lam=1e-4,
                       downlink_compressor=C.make_compressor(
                           "scalarq(bits=4)"))
    opt = sgd(0.1)
    state = TrainState.create(model.init(jax.random.PRNGKey(0)), opt)
    batch = {"image": jax.random.normal(jax.random.PRNGKey(1),
                                        (4, 28, 28, 1)),
             "label": jnp.zeros((4,), jnp.int32)}
    plain = make_train_step(model, opt, donate=False)
    s_a, _ = plain(state, batch)
    s_b, _ = plain(state, batch)
    for a, b in zip(jax.tree.leaves(s_a.params), jax.tree.leaves(s_b.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    keyed1 = make_train_step(model, opt, donate=False,
                             step_key=jax.random.PRNGKey(7))
    keyed2 = make_train_step(model, opt, donate=False,
                             step_key=jax.random.PRNGKey(8))
    k1, _ = keyed1(state, batch)
    k2, _ = keyed2(state, batch)
    diffs = [bool(jnp.any(a != b)) for a, b in
             zip(jax.tree.leaves(k1.params), jax.tree.leaves(k2.params))]
    assert any(diffs)                             # stochastic rounding bites


def test_trainer_warm_start_stacked_state_cold_falls_back_on_cohort_change():
    """Per-client/per-row stacked quantizer state (codebooks rank > 3 —
    TransformerLM per-sequence vmap, paper models with client_batch > 0)
    only fits a cohort of the size that produced it: a different
    participant count must fall back to a cold round instead of vmapping
    mismatched axes."""
    from repro.core.quantizer import QuantizerState
    from repro.federated.scheduler import Arrival

    tr = _trainer(warm_start=True)
    state = tr.init_state(jax.random.PRNGKey(0))
    part = tr.client_batch_for(0, jax.random.PRNGKey(1))
    arr = lambda cid: Arrival(client=cid, version=0, t_arrival=0.0)

    # cohort-size-independent state (rank 3): reused across any count
    tr._global_q = QuantizerState(codebooks=jnp.zeros((1, 4, 32)),
                                  rounds=jnp.ones((), jnp.int32))
    tr._global_q_nparts = 4
    cs = tr._cut_state_for([arr(0), arr(1)], state.params, [part],
                           stacked=True)
    assert cs.quantizer is not None
    # stacked state (rank 4, one slot per client/row): count change -> cold
    tr._global_q = QuantizerState(codebooks=jnp.zeros((4, 1, 4, 32)),
                                  rounds=jnp.ones((4,), jnp.int32))
    tr._global_q_nparts = 4
    cs = tr._cut_state_for([arr(0), arr(1)], state.params, [part],
                           stacked=True)
    assert cs.quantizer is None
    cs = tr._cut_state_for([arr(0), arr(1), arr(2), arr(3)], state.params,
                           [part], stacked=True)
    assert cs.quantizer is not None
