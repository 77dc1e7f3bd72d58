"""Parameter partitioning rules: param path + shape -> PartitionSpec.

Scheme (Megatron-style TP over the "model" axis + FSDP over "data"):

  * column-parallel weights (QKV / up / gate projections, LM head, experts'
    up-projections): last (output) dim -> "model", input d_model dim -> "data"
  * row-parallel weights (attention output / down projections): input dim ->
    "model", output d_model dim -> "data"
  * token embedding: vocab -> "model", d_model -> "data"
  * MoE expert stacks (E, din, dout): experts -> "model" when E divides the
    model-axis size (expert parallelism), otherwise TP inside each expert
  * norms / small vectors: replicated

Every axis assignment is guarded by divisibility against the installed mesh:
if a dim does not divide the axis size, that axis is dropped (replicated on
that dim) instead of failing. Stacked per-layer params (leading scan dim)
get a leading ``None``.
"""

from __future__ import annotations

import re
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.sharding.ctx import current_mesh, filter_spec

# (regex on the param path, spec builder keyed by rank)
# Specs below are written for the *unstacked* shape; a leading scan dim is
# handled by the caller.
_RULES = [
    # embeddings & heads -------------------------------------------------
    # vocab dim REPLICATED on purpose: a row-gather from a vocab-sharded table
    # forces SPMD "involuntary full rematerialization" (replicates the gather
    # output); d_model-sharded tables gather locally. LM heads stay
    # column-parallel over vocab.
    (r"(^|/)tok_embed$", {2: P(None, "data"), 3: P(None, None, "data")}),
    (r"(^|/)pos_embed$", {2: P(None, "data")}),
    (r"(^|/)head(_\d+)?$", {2: P("data", "model"), 3: P(None, "data", "model")}),
    (r"(^|/)vision_proj$", {2: P(None, "data")}),
    # attention ----------------------------------------------------------
    (r"/(wq|wk|wv|wkv_a|wkv_b)$", {2: P("data", "model")}),
    (r"/wo$", {2: P("model", "data")}),
    (r"/(wq_b|wk_b|wv_b)$", {1: P("model")}),
    (r"/wo_b$", {1: P("data")}),
    # dense mlp ----------------------------------------------------------
    (r"/(w_gate|w_up)$", {2: P("data", "model")}),
    (r"/w_down$", {2: P("model", "data")}),
    (r"/(w_gate_b|w_up_b)$", {1: P("model")}),
    (r"/w_down_b$", {1: P("data")}),
    # MoE ----------------------------------------------------------------
    (r"/router$", {2: P("data", None)}),
    # expert-parallel when E divides the model axis; otherwise Megatron
    # column/row parallel INSIDE each expert (+ FSDP over data) — a small
    # expert count must still shard its d_ff over "model" or expert params
    # alone blow past HBM (mixtral: 13.8 GiB/device without it)
    (r"/(we_gate|we_up)$", {3: ("EXPERT", P("model", "data", None), P(None, "data", "model"))}),
    (r"/we_down$", {3: ("EXPERT", P("model", None, "data"), P(None, "model", "data"))}),
    # SSM (mamba2) ---------------------------------------------------------
    (r"/in_proj(_z|_xbc|_dt)?$", {2: P("data", "model")}),
    (r"/out_proj$", {2: P("model", "data")}),
    (r"/conv_w$", {2: P(None, "model")}),
    (r"/conv_b$", {1: P("model")}),
    (r"/(dt_bias|A_log|ssm_D)$", {1: P(None)}),
    # conv frontends (paper CNN example) ----------------------------------
    (r"/conv\d_w$", {4: P(None, None, None, "model")}),
    (r"/conv\d_b$", {1: P("model")}),
    (r"/(dense\d_w|lstm_.*|emb_w)$", {2: P("data", "model")}),
]


def _fits(dim: int, entry, mesh: Mesh) -> bool:
    if entry is None:
        return True
    names = (entry,) if isinstance(entry, str) else tuple(entry)
    total = 1
    for n in names:
        if n not in mesh.axis_names:
            return False
        total *= mesh.shape[n]
    return dim % total == 0


def _guard(spec: P, shape, mesh: Mesh) -> P:
    """Drop spec axes that do not divide the corresponding dim."""
    spec = filter_spec(spec, mesh)
    entries = list(spec) + [None] * (len(shape) - len(spec))
    return P(*[e if _fits(d, e, mesh) else None for d, e in zip(shape, entries)])


def spec_for_param(path: str, shape, mesh: Optional[Mesh] = None) -> P:
    """PartitionSpec for a parameter identified by its tree path."""
    mesh = mesh if mesh is not None else current_mesh()
    if mesh is None:
        return P()
    stacked = bool(re.search(r"(^|/)layers/", path)) and len(shape) >= 2
    core_shape = shape[1:] if stacked else shape
    for pattern, by_rank in _RULES:
        if re.search(pattern, path):
            rule = by_rank.get(len(core_shape))
            if rule is None:
                continue
            if isinstance(rule, tuple) and rule[0] == "EXPERT":
                # expert-parallel if E divides the model axis, else TP-in-expert
                _, ep_spec, tp_spec = rule
                model = mesh.shape.get("model", 1) if "model" in mesh.axis_names else 1
                spec = ep_spec if core_shape[0] % model == 0 else tp_spec
            else:
                spec = rule
            spec = _guard(spec, core_shape, mesh)
            return P(None, *spec) if stacked else spec
    # default: replicate small things, FSDP-shard big matrices on dim0
    if len(core_shape) >= 2:
        spec = _guard(P("data"), core_shape, mesh)
        return P(None, *spec) if stacked else spec
    return P()


def _paths(tree, prefix=""):
    for k, v in tree.items():
        p = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            yield from _paths(v, p)
        else:
            yield p, v


def param_specs(params, mesh: Optional[Mesh] = None):
    """Build a pytree of PartitionSpecs matching ``params``."""
    mesh = mesh if mesh is not None else current_mesh()

    def walk(tree, prefix=""):
        out = {}
        for k, v in tree.items():
            p = f"{prefix}/{k}" if prefix else k
            if isinstance(v, dict):
                out[k] = walk(v, p)
            else:
                out[k] = spec_for_param(p, v.shape, mesh)
        return out

    return walk(params)


def inference_spec(spec: P, shape, mesh: Optional[Mesh] = None) -> P:
    """Re-layout a training spec for decode serving: fold the FSDP ("data")
    dim into the TP dim instead.

    Training shards matrices (FSDP x TP) so optimizer state fits; decode has
    no optimizer state but all-gathers every FSDP-sharded weight for each
    generated token — the dominant collective cost of serving. Merging
    "data" into the tensor-parallel dim keeps params fully sharded with NO
    per-token weight gathering (the per-layer activation all-reduce spans
    the merged group instead). Falls back to the original spec when the TP
    dim does not divide the merged axis.
    """
    mesh = mesh if mesh is not None else current_mesh()
    if mesh is None:
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))

    def names(e):
        return () if e is None else ((e,) if isinstance(e, str) else tuple(e))

    data_dims = [i for i, e in enumerate(entries) if "data" in names(e)]
    model_dims = [i for i, e in enumerate(entries) if "model" in names(e)]
    if not data_dims or not model_dims or data_dims[0] == model_dims[0]:
        return spec
    di, mi = data_dims[0], model_dims[0]
    merged = tuple(n for n in names(entries[mi]) if n != "data") + ("data",)
    new = list(entries)
    new[di] = tuple(n for n in names(entries[di]) if n != "data") or None
    if isinstance(new[di], tuple) and len(new[di]) == 1:
        new[di] = new[di][0]
    new[mi] = merged if len(merged) > 1 else merged[0]
    cand = _guard(P(*new), shape, mesh)
    # only accept if the merged axis actually divides (guard keeps it)
    if "data" in names(list(cand)[mi] if mi < len(list(cand)) else None):
        return cand
    return spec


def inference_param_specs(params, mesh: Optional[Mesh] = None):
    """param_specs re-laid-out for serving (see inference_spec)."""
    mesh = mesh if mesh is not None else current_mesh()

    def walk(tree, prefix=""):
        out = {}
        for k, v in tree.items():
            p = f"{prefix}/{k}" if prefix else k
            if isinstance(v, dict):
                out[k] = walk(v, p)
            else:
                out[k] = inference_spec(spec_for_param(p, v.shape, mesh),
                                        v.shape, mesh)
        return out

    return walk(params)


def param_shardings(params, mesh: Optional[Mesh] = None):
    """Like param_specs but returns NamedShardings (or None without a mesh)."""
    mesh = mesh if mesh is not None else current_mesh()
    if mesh is None:
        return jax.tree.map(lambda _: None, params)
    specs = param_specs(params, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))
