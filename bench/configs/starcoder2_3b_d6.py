"""Builder for the ``starcoder2_3b_d6`` configuration
(``starcoder2_3b_d6.json``): StarCoder2-3B at published widths, 6 of its 30
layers (4 client, 2 server).

The harness finds this file by the configuration's name. It describes the
parameters (made by ``bench/params.py`` from the seed, in the program's
layout: per-layer leaves stacked over the layers of each half), builds the
program's `FederatedTrainer` from the sizes in the JSON file, and states the
model FLOPs and the cut quantizer's shapes per server update.
"""

from __future__ import annotations


def _stack(n, a):
    D, Q = a["d_model"], a["num_heads"] * a["head_dim"]
    KV, F = a["num_kv_heads"] * a["head_dim"], a["d_ff"]
    bf = a["param_dtype"]
    norm = lambda: {"scale": ((n, D), bf, "one", 0.05),
                    "bias": ((n, D), bf, "normal", 0.02)}
    w = lambda i, o: ((n, i, o), bf, "fanin", 1.0, True)
    b = lambda o: ((n, o), bf, "normal", 0.02)
    return {"p0": {
        "ln1": norm(), "ln2": norm(),
        "mixer": {"wq": w(D, Q), "wk": w(D, KV), "wv": w(D, KV),
                  "wo": w(Q, D), "wq_b": b(Q), "wk_b": b(KV), "wv_b": b(KV),
                  "wo_b": b(D)},
        "ffn": {"w_up": w(D, F), "w_down": w(F, D), "w_up_b": b(F),
                "w_down_b": b(D)},
    }}


def param_layout(cfg):
    """{half: {...: (shape, dtype, init, scale[, stacked])}}, the program's
    layout (TransformerLM.init)."""
    a = cfg["arch"]
    D, V, bf = a["d_model"], a["vocab_size"], a["param_dtype"]
    cut = a["cut_periods"]
    return {
        "client": {"tok_embed": ((V, D), bf, "normal", 0.02),
                   "layers": _stack(cut, a)},
        "server": {"layers": _stack(a["num_layers"] - cut, a),
                   "final_norm": {"scale": ((D,), bf, "one", 0.05),
                                  "bias": ((D,), bf, "normal", 0.02)},
                   "head": ((D, V), bf, "fanin", 1.0)},
    }


def build_trainer(cfg, mix, seed, data):
    """The program's trainer for this cell, on the pooled traffic."""
    from repro.configs.base import ArchConfig
    from repro.core.quantizer import PQConfig
    from repro.federated import FederatedTrainer
    from repro.models.transformer import TransformerLM
    from repro.optim import adam
    o = cfg["optimizer"]
    if o["name"] != "adam":
        raise ValueError("starcoder2_3b_d6 states Adam")
    arch = ArchConfig(**cfg["arch"])
    model = TransformerLM(arch, pq=PQConfig(**cfg["pq"]), lam=cfg["lam"])
    return FederatedTrainer(
        model, adam(o["lr"], o["b1"], o["b2"], o["eps"]), data,
        cohort=int(mix["cohort"]), client_batch=int(mix["client_batch"]),
        seed=seed, executor=mix.get("executor", "stacked"),
        downlink_compressor=cfg["downlink"])


def model_flops_per_update(cfg, mix):
    """Forward + backward FLOPs (3x the forward) of every matmul of both
    halves and the LM head, plus causal attention (the half of QK^T and PV
    below the diagonal), for one server update, from shapes. The embedding
    lookup, norms, the quantizer and the optimizer are not counted, nor is
    recomputation under rematerialization."""
    a = cfg["arch"]
    D, F, V = a["d_model"], a["d_ff"], a["vocab_size"]
    Q = a["num_heads"] * a["head_dim"]
    KV = a["num_kv_heads"] * a["head_dim"]
    rows = int(mix["cohort"]) * int(mix["client_batch"])
    S = int(mix["seq"])
    T = rows * S
    per_layer = D * Q + 2 * D * KV + Q * D + 2 * D * F
    matmul = 2 * T * (a["num_layers"] * per_layer + D * V)
    attn = a["num_layers"] * 2 * rows * S * S * Q   # 2 matmuls, causal half
    return float(3 * (matmul + attn))


def pq_work(cfg, mix):
    """The cut quantizer's shapes per server update: each sequence is one
    client's cut, quantized apart."""
    q = cfg["pq"]["num_subvectors"]
    return {"clients": int(mix["cohort"]) * int(mix["client_batch"]),
            "points": q * int(mix["seq"]),
            "dim": cfg["arch"]["d_model"] // q,
            "clusters": cfg["pq"]["num_clusters"],
            "iters": cfg["pq"]["kmeans_iters"]}
