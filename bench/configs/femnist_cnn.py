"""Builder for the ``femnist_cnn`` configuration (``femnist_cnn.json``).

The harness finds this file by the configuration's name. It describes the
parameters (made by ``bench/params.py`` from the seed, in the program's
layout), builds the program's `FederatedTrainer` on the pooled traffic, and
states the model FLOPs and the cut quantizer's shapes per server update.
"""

from __future__ import annotations

import math


def param_layout(cfg):
    """{half: {leaf: (shape, dtype, init, scale)}}, the program's layout."""
    ly = cfg["layers"]
    f32 = "float32"
    return {
        "client": {"conv1_w": (tuple(ly["conv1"]), f32, "fanin", 2 ** 0.5),
                   "conv1_b": ((ly["conv1"][3],), f32, "normal", 0.05),
                   "conv2_w": (tuple(ly["conv2"]), f32, "fanin", 2 ** 0.5),
                   "conv2_b": ((ly["conv2"][3],), f32, "normal", 0.05)},
        "server": {"dense1_w": (tuple(ly["dense1"]), f32, "fanin", 2 ** 0.5),
                   "dense1_b": ((ly["dense1"][1],), f32, "normal", 0.05),
                   "dense2_w": (tuple(ly["dense2"]), f32, "fanin", 2 ** 0.5),
                   "dense2_b": ((ly["dense2"][1],), f32, "normal", 0.05)},
    }


def build_trainer(cfg, mix, seed, data):
    """The program's trainer for this cell, on the pooled traffic."""
    from repro.core.quantizer import PQConfig
    from repro.federated import FederatedTrainer
    from repro.models.paper_models import FemnistCNN
    from repro.optim import sgd
    if cfg["optimizer"]["name"] != "sgd":
        raise ValueError("femnist_cnn states SGD")
    model = FemnistCNN(num_classes=cfg["num_classes"], pq=PQConfig(**cfg["pq"]),
                       lam=cfg["lam"], client_batch=int(mix["client_batch"]))
    return FederatedTrainer(
        model, sgd(cfg["optimizer"]["lr"]), data,
        cohort=int(mix["cohort"]), client_batch=int(mix["client_batch"]),
        seed=seed, executor=mix.get("executor", "stacked"),
        downlink_compressor=cfg["downlink"])


def model_flops_per_update(cfg, mix):
    """Forward + backward FLOPs of both halves for one server update, from
    shapes: 2 per multiply-add; the backward pass is twice the forward,
    except that conv1 needs no gradient of its input. The quantizer, the
    codecs and the optimizer are not model FLOPs."""
    ly = cfg["layers"]
    n = int(mix["cohort"]) * int(mix["client_batch"])
    kh, kw, cin, c1 = ly["conv1"]
    h1 = 28 - kh + 1
    conv1 = 2 * h1 * h1 * c1 * kh * kw * cin
    kh, kw, _, c2 = ly["conv2"]
    h2 = h1 - kh + 1
    conv2 = 2 * h2 * h2 * c2 * kh * kw * c1
    d1 = 2 * math.prod(ly["dense1"])
    d2 = 2 * math.prod(ly["dense2"])
    return float(n * (3 * (conv1 + conv2 + d1 + d2) - conv1))


def pq_work(cfg, mix):
    """The cut quantizer's shapes per server update: clients quantized
    apart, subvectors (points) per client, their width, clusters, Lloyd
    iterations."""
    q = cfg["pq"]["num_subvectors"]
    return {"clients": int(mix["cohort"]),
            "points": q * int(mix["client_batch"]),
            "dim": cfg["cut_dim"] // q,
            "clusters": cfg["pq"]["num_clusters"],
            "iters": cfg["pq"]["kmeans_iters"]}
