"""Builder for the ``moonlight_16b_a3b_ep8_d6`` configuration
(``moonlight_16b_a3b_ep8_d6.json``): Moonlight-16B-A3B at published widths
on one chip of an 8-chip expert-parallel group, 6 of its 27 layers (the
client's dense layer 0 and 5 expert layers of the server), experts 0-7 of
64 in each expert layer, the first eighth of the vocabulary.

The harness finds this file by the configuration's name. It describes the
parameters in the program's layout, builds the program's
`FederatedTrainer`, and states, from shapes, the model FLOPs, the cut
quantizer's shapes and the held experts' grouped matmuls per server update.
The JSON file holds the published config's keys at its top level and the
program's `ArchConfig` under ``arch``; ``arch`` checks that they agree.
"""

from __future__ import annotations

import math

BF16 = 2

# published key -> the program's ArchConfig field that must equal it
SAME = {"hidden_size": "d_model", "intermediate_size": "d_ff",
        "kv_lora_rank": "kv_lora_rank", "moe_intermediate_size": "moe_d_ff",
        "n_routed_experts": "num_experts", "n_shared_experts":
        "num_shared_experts", "num_attention_heads": "num_heads",
        "num_key_value_heads": "num_kv_heads", "num_experts_per_tok":
        "experts_per_token", "num_hidden_layers": "num_layers",
        "qk_rope_head_dim": "qk_rope_dim", "v_head_dim": "v_head_dim",
        "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
        "routed_scaling_factor": "routed_scaling", "vocab_size": "vocab_size",
        "experts_held": "experts_held", "first_k_dense_replace":
        "first_dense_layers"}


def arch(cfg):
    """The ``arch`` group, checked against the published keys."""
    a = cfg["arch"]
    for pub, field in SAME.items():
        if a[field] != cfg[pub]:
            raise ValueError(f"arch.{field} = {a[field]} but {pub} = "
                             f"{cfg[pub]}")
    if a["head_dim"] != cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]:
        raise ValueError("arch.head_dim must be qk_nope + qk_rope")
    if cfg["q_lora_rank"] is not None or cfg["n_group"] != 1 or \
            cfg["scoring_func"] != "sigmoid" or not cfg["norm_topk_prob"]:
        raise ValueError("the program's layer is DeepSeek-V3's with no query "
                         "latent, one group, sigmoid scores renormalized")
    return a


def _stack(n, a, moe):
    D, H, bf = a["d_model"], a["num_heads"], a["param_dtype"]
    r, rope, vd = a["kv_lora_rank"], a["qk_rope_dim"], a["v_head_dim"]
    nope = a["head_dim"] - rope
    w = lambda i, o, scale=1.0: ((n, i, o), bf, "fanin", scale, True)
    norm = lambda d: {"scale": ((n, d), bf, "one", 0.05)}
    layer = {
        "ln1": norm(D), "ln2": norm(D),
        "mixer": {"wq": w(D, H * a["head_dim"]), "wkv_a": w(D, r + rope),
                  "kv_norm": norm(r), "wkv_b": w(r, H * (nope + vd)),
                  "wo": w(H * vd, D)},
    }
    if not moe:
        F = a["d_ff"]
        layer["ffn"] = {"w_gate": w(D, F), "w_up": w(D, F),
                        "w_down": w(F, D)}
        return {"p0": layer}
    E, Eh, F = a["num_experts"], a["experts_held"], a["moe_d_ff"]
    Fs = F * a["num_shared_experts"]
    # the fan-in counts every axis but the stack's and the last, so an
    # expert stack (n, Eh, d_in, d_out) is scaled back by sqrt(Eh)
    ew = lambda i, o: ((n, Eh, i, o), bf, "fanin", math.sqrt(Eh), True)
    layer["ffn"] = {
        "router": ((n, D, E), "float32", "fanin", 1.0, True),
        "router_bias": ((n, E), "float32", "normal", 0.01),
        "we_gate": ew(D, F), "we_up": ew(D, F), "we_down": ew(F, D),
        "shared": {"w_gate": w(D, Fs), "w_up": w(D, Fs), "w_down": w(Fs, D)},
    }
    return {"p0": layer}


def param_layout(cfg):
    """{half: {...: (shape, dtype, init, scale[, stacked])}}, the program's
    layout (TransformerLM.init): the client's stack is the dense layer 0,
    the server's the expert layers."""
    a = arch(cfg)
    D, V, bf = a["d_model"], a["vocab_size"], a["param_dtype"]
    cut = a["cut_periods"]
    return {
        "client": {"tok_embed": ((V, D), bf, "normal", 0.02),
                   "layers": _stack(cut, a, moe=False)},
        "server": {"layers": _stack(a["num_layers"] - cut, a, moe=True),
                   "final_norm": {"scale": ((D,), bf, "one", 0.05)},
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
        raise ValueError("moonlight_16b_a3b_ep8_d6 states Adam")
    model = TransformerLM(ArchConfig(**arch(cfg)), pq=PQConfig(**cfg["pq"]),
                          lam=cfg["lam"])
    return FederatedTrainer(
        model, adam(o["lr"], o["b1"], o["b2"], o["eps"]), data,
        cohort=int(mix["cohort"]), client_batch=int(mix["client_batch"]),
        seed=seed, executor=mix.get("executor", "stacked"),
        downlink_compressor=cfg["downlink"])


def _tokens(mix):
    rows = int(mix["cohort"]) * int(mix["client_batch"])
    return rows, int(mix["seq"])


def held_rows(a, tokens):
    """Rows the held experts get per layer at a balanced load: each token's
    k picks spread evenly over the experts."""
    return tokens * a["experts_per_token"] * a["experts_held"] \
        / a["num_experts"]


def model_flops_per_update(cfg, mix):
    """Forward + backward FLOPs (3x the forward) for one server update,
    from shapes: MLA's projections, causal attention (the half of QKᵀ at
    the query/key width and of PV at the value width below the diagonal),
    the dense layer, per expert layer the router, the shared experts and
    the held experts at a balanced load (k x held / experts expert per
    token), and the LM head. The embedding lookup, norms, the quantizer and
    the optimizer are not counted, nor is recomputation under
    rematerialization."""
    a = arch(cfg)
    D, V, H = a["d_model"], a["vocab_size"], a["num_heads"]
    hd, vd, r = a["head_dim"], a["v_head_dim"], a["kv_lora_rank"]
    rope = a["qk_rope_dim"]
    rows, S = _tokens(mix)
    T = rows * S
    dense, L = a["first_dense_layers"], a["num_layers"]
    attn = D * H * hd + D * (r + rope) + r * H * (hd - rope + vd) + H * vd * D
    shared = 3 * D * a["moe_d_ff"] * a["num_shared_experts"]
    held = 3 * D * a["moe_d_ff"] * held_rows(a, 1)
    per_token = L * attn + dense * 3 * D * a["d_ff"] \
        + (L - dense) * (D * a["num_experts"] + shared + held) + D * V
    matmul = 2 * T * per_token
    scores = L * rows * S * S * H * (hd + vd)   # 2 matmuls, causal half
    return float(3 * (matmul + scores))


def pq_work(cfg, mix):
    """The cut quantizer's shapes per server update: each sequence is one
    client's cut, quantized apart."""
    q = cfg["pq"]["num_subvectors"]
    return {"clients": int(mix["cohort"]) * int(mix["client_batch"]),
            "points": q * int(mix["seq"]),
            "dim": cfg["arch"]["d_model"] // q,
            "clusters": cfg["pq"]["num_clusters"],
            "iters": cfg["pq"]["kmeans_iters"]}


def moe_work(cfg, mix):
    """The held experts' grouped matmuls per server update, from shapes at a
    balanced load, in bfloat16: per expert layer three products (gate, up,
    down), each run forward, for the input's gradient and for the weights'
    gradient (recomputation under rematerialization not counted). Each of
    the nine reads its two operands once and writes its result once."""
    a = arch(cfg)
    D, F, Eh = a["d_model"], a["moe_d_ff"], a["experts_held"]
    rows, S = _tokens(mix)
    R = held_rows(a, rows * S)
    layers = a["num_layers"] - a["first_dense_layers"]
    per_product = 2.0 * R * D * F
    per_pass = BF16 * (R * (D + F) + Eh * D * F)
    return {"flops": layers * 9 * per_product,
            "bytes": layers * 9 * float(per_pass)}
