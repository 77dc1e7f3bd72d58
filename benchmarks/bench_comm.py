"""Paper Table 1 + §5 worked example: communication accounting.

Emits per-algorithm uplink bits for the paper's FEMNIST setting and for two
assigned big archs, and checks the §5 numbers: 490x activation compression;
~10x total-uplink reduction vs SplitFed; ~62x vs FedAvg with ~64x fewer
client-side trainable parameters.

Accounting width: the §5 worked example is checked at the paper's fixed
phi = 64 bits (PQConfig's default ``phi_bits``), passed explicitly below —
``tree_bits``/``comm_report`` now default to the *actual* dtype width, so
the big-arch rows report dtype-derived phi (32 for fp32 smoke configs).

The ``femnist_wire_measured`` row closes the loop analytically asserted
above: it pushes a real quantized batch through the bit-packed wire codec
(``federated/wire.py``) and reports measured payload bytes next to
``PQConfig.message_bits`` at the wire width — they must agree to within
the 24-byte header (+ <1 byte of code padding).

The ``femnist_downlink_measured`` row does the same for the OTHER
direction: the cut-layer gradient message through the acceptance downlink
codec (``chain:topk(k=0.1)+scalarq(bits=8)``) vs the dense fp32 baseline —
the measured reduction must be >= 8x and agree with the compressor's
``analytic_bits`` to within the per-stage headers.

The ``pq_delta`` rows measure the cross-round codebook-reuse win: round 1's
codebook (warm-started Lloyd) is shipped as the ``pq-delta`` wire kind —
8-bit quantized deltas against the acked round-0 reference — and the
measured codebook component must shrink >= 1.5x vs fresh fp16 codebooks
(asserted; acceptance criterion), with the closed-loop reconstruction
decoding bit-exactly.

The ``pq_delta_downlink`` row closes the same loop for the OTHER
direction: a pq downlink ships the cut-layer *gradient* as
codebooks+codes, and until the ``downlink`` hook took codec state
(``core/compressors.downlink``) those codebooks were
fresh every round. A realistic gradient proxy (round-1 gradient = a small
drift of round-0's) is quantized warm-started from round 0's
`QuantizerState` and its codebook delta-encoded against the acked
reference — the measured downlink codebook component must shrink >= 1.5x
(asserted), decoding bit-exactly."""

from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import emit, write_bench_json
from repro.configs.base import get_arch
from repro.core.compressors import make_compressor
from repro.core.fedlite import comm_report
from repro.core.quantizer import PQConfig, quantize
from repro.core.split import split_summary
from repro.federated import wire
from repro.launch.specs import default_pq, make_model
from repro.models.paper_models import FemnistCNN

PAPER_PHI = 64  # the paper's fixed accounting float width (bits)


def run(fast: bool = True):
    rows = []
    # ---- the paper's FEMNIST worked example (phi = 64, as in §5) ----------
    pq = PQConfig(num_subvectors=1152, num_clusters=2, kmeans_iters=2,
                  phi_bits=PAPER_PHI)
    model = FemnistCNN(pq=pq, lam=1e-4)
    params = model.init(jax.random.PRNGKey(0))
    s = split_summary(params, phi_bits=PAPER_PHI)
    B, d = 20, 9216
    act_bits = PAPER_PHI * d * B
    msg = pq.message_bits(B, d)
    client_bits = s["client_bits"]
    total_bits = client_bits + s["server_bits"]
    rows.append({
        "name": f"femnist_b20_q1152_L2_phi{PAPER_PHI}",
        "us_per_call": 0.0,
        "activation_compression": round(act_bits / msg, 1),        # paper: 490
        "uplink_vs_splitfed": round((client_bits + act_bits) /
                                    (client_bits + msg), 1),       # paper: ~10
        "uplink_vs_fedavg": round(total_bits / (client_bits + msg), 1),
        "client_param_fraction": round(s["client_fraction"], 4),   # ~1.6%
    })

    # ---- measured wire bytes vs the analytic bit count ---------------------
    # one real PQ encode through the bit-packed codec; fp16 codebooks on the
    # wire, so the analytic reference is message_bits at phi=16
    acts = jax.random.normal(jax.random.PRNGKey(1), (B, d))
    payload = wire.encode_bytes(quantize(acts, pq), "float16")
    analytic_bits = pq.message_bits(B, d, phi_bits=16)
    overhead_bits = len(payload) * 8 - analytic_bits
    assert len(payload) * 8 == wire.wire_bits(pq, B, d, "float16"), \
        "measured payload disagrees with wire_bits"
    assert 0 <= overhead_bits <= (wire.HEADER_BYTES + wire.CRC_BYTES) * 8 + 7, \
        f"wire overhead {overhead_bits} bits exceeds the documented frame"
    rows.append({
        "name": "femnist_wire_measured_b20_q1152_L2",
        "us_per_call": 0.0,
        "measured_bytes": len(payload),
        "analytic_phi16_bits": analytic_bits,
        "header_overhead_bits": overhead_bits,
        "measured_compression_vs_fp32": round(
            32 * d * B / (len(payload) * 8), 1),
    })

    # ---- measured DOWNLINK bytes: compressed gradient vs dense -------------
    # the cut-layer gradient message (shape-alike stand-in: the activations)
    # through the acceptance-criteria chain codec; dense fp32 is what the
    # pre-refactor downlink shipped every round
    dl = make_compressor("chain:topk(k=0.1)+scalarq(bits=8)")
    comp = dl.compress(acts)
    dl_payload = dl.wire_payload(comp)
    dense_bytes = acts.size * 4
    dl_analytic = dl.analytic_bits(B, d, phi_bits=32)
    reduction = dense_bytes / len(dl_payload)
    assert reduction >= 8.0, \
        f"downlink reduction {reduction:.2f}x below the 8x acceptance bar"
    # wire overhead: header + CRC trailer per chain stage + <1 B pad each
    dl_overhead = len(dl_payload) * 8 - dl_analytic
    assert 0 <= dl_overhead <= \
        2 * ((wire.HEADER_BYTES + wire.CRC_BYTES) * 8 + 7), \
        f"downlink wire overhead {dl_overhead} bits exceeds stage frames"
    rec = wire.reconstruct(wire.decode_payload(dl_payload))
    assert np.isfinite(rec).all()
    rows.append({
        "name": "femnist_downlink_measured_b20_topk0.1_sq8",
        "us_per_call": 0.0,
        "measured_bytes": len(dl_payload),
        "dense_bytes": dense_bytes,
        "analytic_bits": dl_analytic,
        "header_overhead_bits": dl_overhead,
        "measured_downlink_reduction": round(reduction, 1),
    })

    # ---- measured pq-delta codebook bytes vs fresh fp16 codebooks ----------
    # the LM-cut-shaped config (d/q = 8, L = 16 — launch/specs.default_pq):
    # this is where codebook bytes matter; FEMNIST's L=2 codebook is 32 B.
    # One recipe, both directions: round 0 ships full fp16 codebooks, the
    # receiver's decode is the acked reference, round 1 quantizes
    # warm-started and ships b-bit codebook deltas — bit-exact closed loop,
    # measured codebook component must shrink >= 1.5x (asserted).
    from repro.core.quantizer import quantize_stateful
    d_lm, q_lm = 512, 64
    pq_lm = PQConfig(num_subvectors=q_lm, num_clusters=16, kmeans_iters=4)

    def measure_pq_delta(t0, t1, row_name):
        qb0, qstate = quantize_stateful(t0, pq_lm)
        ref = wire.decode_bytes(
            wire.encode_bytes(qb0, "float16")).codebooks.astype(np.float32)
        qb1_, _ = quantize_stateful(t1, pq_lm, qstate)       # warm round
        full = wire.encode_bytes(qb1_, "float16")
        delta, recon = wire.encode_pq_delta(qb1_, ref, delta_bits=8)
        assert len(delta) * 8 == wire.pq_delta_wire_bits(
            pq_lm, t1.shape[0], d_lm, 8)
        wb = wire.decode_pq_delta(delta, ref)
        assert (wb.codes == np.asarray(qb1_.codes)).all()
        np.testing.assert_array_equal(wb.codebooks, recon)  # closed loop
        cb_full = int(np.prod(pq_lm.codebook_shape(d_lm))) * 2  # fp16 bytes
        # frame = header + body + CRC trailer in both directions; the
        # delta body's epoch word/scale live in its codebook component
        code_bytes = len(full) - wire.HEADER_BYTES - wire.CRC_BYTES - cb_full
        cb_delta = len(delta) - wire.HEADER_BYTES - wire.CRC_BYTES \
            - code_bytes
        reduction = cb_full / cb_delta
        assert reduction >= 1.5, \
            f"{row_name}: codebook reduction {reduction:.2f}x below 1.5x"
        return {
            "name": row_name,
            "us_per_call": 0.0,
            "codebook_bytes_full_fp16": cb_full,
            "codebook_bytes_delta": cb_delta,
            "codebook_reduction": round(reduction, 2),
            "payload_bytes_full": len(full),
            "payload_bytes_delta": len(delta),
            "delta_recon_max_err": round(
                float(np.abs(recon - np.asarray(qb1_.codebooks,
                                                np.float32)).max()), 6),
        }

    # uplink: round-1 activations drifted slightly from round 0's
    acts1 = jax.random.normal(jax.random.PRNGKey(2), (256, d_lm))
    acts2 = acts1 + 0.05 * jax.random.normal(jax.random.PRNGKey(3),
                                             (256, d_lm))
    rows.append(measure_pq_delta(acts1, acts2,
                                 "pq_delta_measured_lmcut_d512_L16_b8"))
    # downlink: the gradient message of a pq downlink, steady state — the
    # stateful-downlink (``downlink`` hook with state) analogue of the row
    # above, at gradient scale
    g1 = 0.01 * jax.random.normal(jax.random.PRNGKey(4), (256, d_lm))
    g2 = g1 + 0.05 * 0.01 * jax.random.normal(jax.random.PRNGKey(5),
                                              (256, d_lm))
    rows.append(measure_pq_delta(
        g1, g2, "pq_delta_downlink_measured_lmcut_d512_L16_b8"))

    # ---- big-arch accounting (smoke-size params, dtype-derived phi) --------
    for arch in ["llama3_8b", "mixtral_8x22b"]:
        cfg = get_arch(arch, smoke=True)
        m = make_model(cfg)
        p = m.init(jax.random.PRNGKey(0))
        rep = comm_report(m, p, tokens_per_client=4096)
        rows.append({
            "name": f"{arch}_smoke_tokens4096",
            "us_per_call": 0.0,
            "phi_bits": rep["phi_bits"],
            "activation_compression": round(
                rep["activation_compression_ratio"], 1),
            "uplink_vs_splitfed": round(
                rep["uplink_reduction_vs_splitfed"], 2),
            "uplink_vs_fedavg": round(rep["uplink_reduction_vs_fedavg"], 2),
        })

    # ---- full-size analytic accounting (no allocation; dtype-derived phi) --
    for arch in ["gemma_7b", "command_r_35b"]:
        cfg = get_arch(arch)
        pq_full = default_pq(cfg)
        tokens = 4096
        phi = jax.numpy.dtype(cfg.dtype).itemsize * 8
        act_bits = phi * cfg.d_model * tokens
        msg = pq_full.message_bits(tokens, cfg.d_model, phi_bits=phi)
        rows.append({
            "name": f"{arch}_full_analytic_phi{phi}",
            "us_per_call": 0.0,
            "activation_compression": round(act_bits / msg, 1),
            "head_params_fraction": round(
                cfg.padded_vocab * cfg.d_model / cfg.param_count(), 3),
        })
    # serialize before emit() strips the row keys
    write_bench_json(
        "comm", rows,
        note="Table 1 / §5 accounting: analytic bit counts plus measured "
             "wire payloads (pq, downlink chain, pq-delta codebooks)")
    return rows


def main(fast: bool = True):
    emit(run(fast), "table1_comm")


if __name__ == "__main__":
    main()
