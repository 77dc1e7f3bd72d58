"""ShapeDtypeStruct builders for the dry-run: every model input, train state
and KV/SSM cache as an abstract, sharded stand-in (no device allocation).

All shardings are guarded by divisibility (a dim that does not divide the
mesh axis falls back to the next candidate or replication) so one spec
builder serves every (arch × input shape × mesh) combination.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, InputShape
from repro.core.quantizer import PQConfig
from repro.models.transformer import TransformerLM
from repro.sharding.ctx import filter_spec
from repro.sharding.rules import param_specs


# ---------------------------------------------------------------------------
# default FedLite quantizer for the big archs
# ---------------------------------------------------------------------------

def default_pq(cfg: ArchConfig, *, subvector_dim: int = 8,
               clusters: int = 16, iters: int = 4) -> PQConfig:
    """Paper-faithful defaults scaled to d_model: subvectors of dim 8 (the
    paper's FEMNIST best ratio uses d/q = 8), R=1, L=16. The encode backend
    comes from the arch config ("auto": fused Pallas on TPU, jnp elsewhere);
    ``cfg.pq_warm_iters`` sets the warm-started Lloyd budget for runs that
    carry `QuantizerState` across rounds (None = kmeans_iters // 2)."""
    q = cfg.d_model // subvector_dim
    return PQConfig(num_subvectors=q, num_clusters=clusters, num_groups=1,
                    kmeans_iters=iters, kmeans_chunk=4096,
                    backend=cfg.pq_backend, warm_iters=cfg.pq_warm_iters)


def make_model(cfg: ArchConfig, *, with_pq: bool = True,
               lam: float = 1e-4) -> TransformerLM:
    """Build the split LM with the arch's per-direction cut codecs.

    ``cfg.uplink_compressor`` — "pq" keeps the paper's grouped PQ
    (``with_pq=False`` or "none" disables it → SplitFed); any other spec is
    parsed by ``core/compressors.make_compressor``. ``cfg.downlink_compressor``
    installs a codec on the server→client gradient message ("none": the
    dense baseline, bitwise-identical backward pass).
    """
    from repro.core.compressors import make_compressor
    # the PQ config exists only when the uplink actually runs PQ — a
    # non-pq uplink spec must not leave a misleading model.pq behind
    # (comm_report attributes PQ bits to whatever model.pq says)
    pq = default_pq(cfg) if with_pq and cfg.uplink_compressor == "pq" \
        else None
    uplink = None if cfg.uplink_compressor in ("pq", "none") \
        else make_compressor(cfg.uplink_compressor,
                             pq=default_pq(cfg) if with_pq else None)
    downlink = None if cfg.downlink_compressor == "none" \
        else make_compressor(cfg.downlink_compressor,
                             pq=default_pq(cfg) if with_pq else None)
    return TransformerLM(cfg, pq=pq, lam=lam, uplink_compressor=uplink,
                         downlink_compressor=downlink)


# ---------------------------------------------------------------------------
# sharding helpers
# ---------------------------------------------------------------------------

def _axis_size(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    names = (entry,) if isinstance(entry, str) else entry
    return math.prod(mesh.shape[n] for n in names if n in mesh.axis_names)


def _fit(mesh: Mesh, shape: Tuple[int, ...], *candidates: P) -> P:
    """First candidate spec whose sharded dims all divide; else replicated."""
    for spec in candidates:
        spec_f = filter_spec(spec, mesh)
        entries = list(spec_f) + [None] * (len(shape) - len(spec_f))
        if all(d % _axis_size(mesh, e) == 0 for d, e in zip(shape, entries)):
            return spec_f
    return P()


def _struct(mesh: Mesh, shape, dtype, *candidates: P) -> jax.ShapeDtypeStruct:
    spec = _fit(mesh, tuple(shape), *candidates)
    return jax.ShapeDtypeStruct(tuple(shape), dtype,
                                sharding=NamedSharding(mesh, spec))


BATCH = ("pod", "data")


# ---------------------------------------------------------------------------
# model inputs per input-shape
# ---------------------------------------------------------------------------

def input_specs(cfg: ArchConfig, shape: InputShape, mesh: Mesh,
                *, with_labels: bool = True) -> Dict[str, jax.ShapeDtypeStruct]:
    """Abstract batch for (arch, input shape): tokens/labels (+ modality)."""
    B, S = shape.global_batch, shape.seq_len
    batch: Dict[str, jax.ShapeDtypeStruct] = {}
    tok = jnp.int32
    if cfg.family == "vlm":
        S_vis = int(S * cfg.vision_tokens_frac) // 16 * 16
        S_txt = S - S_vis
        batch["tokens"] = _struct(mesh, (B, S_txt), tok, P(BATCH, None))
        batch["vision_embeds"] = _struct(mesh, (B, S_vis, cfg.vision_embed_dim),
                                         jnp.float32, P(BATCH, None, None))
        batch["positions"] = _struct(mesh, (3, B, S), tok, P(None, BATCH, None))
        if with_labels:
            batch["labels"] = _struct(mesh, (B, S), tok, P(BATCH, None))
    elif cfg.num_codebooks > 1:
        batch["tokens"] = _struct(mesh, (B, cfg.num_codebooks, S), tok,
                                  P(BATCH, None, None))
        if with_labels:
            batch["labels"] = _struct(mesh, (B, cfg.num_codebooks, S), tok,
                                      P(BATCH, None, None))
    else:
        batch["tokens"] = _struct(mesh, (B, S), tok, P(BATCH, None))
        if with_labels:
            batch["labels"] = _struct(mesh, (B, S), tok, P(BATCH, None))
    return batch


def decode_token_specs(cfg: ArchConfig, shape: InputShape, mesh: Mesh):
    B = shape.global_batch
    if cfg.num_codebooks > 1:
        return _struct(mesh, (B, cfg.num_codebooks, 1), jnp.int32,
                       P(BATCH, None, None))
    return _struct(mesh, (B, 1), jnp.int32, P(BATCH, None))


# ---------------------------------------------------------------------------
# cache specs
# ---------------------------------------------------------------------------

def cache_specs(model: TransformerLM, batch_size: int, max_len: int,
                mesh: Mesh, *, seq_shard_budget: int = 4 << 30):
    """Abstract caches with shardings.

    Adaptive policy (§Perf C2): batch-only sharding when the whole cache
    fits ``seq_shard_budget`` bytes/device (no collectives on the decode
    cache update); otherwise the cache-seq dim is additionally sharded over
    "model" (a 32k-token cache for a 30-50L model is tens of GB per batch
    element — seq sharding costs cheap dynamic-update/softmax collectives
    but keeps HBM bounded). SSM states are head-sharded.
    """
    shapes = jax.eval_shape(
        lambda: model.init_caches(batch_size, max_len))

    # total cache bytes/device under batch-only sharding
    batch_shards = _axis_size(mesh, BATCH)
    total = sum(
        s.size * s.dtype.itemsize for s in jax.tree.leaves(shapes))
    per_dev_batch_only = total / max(batch_shards, 1) \
        if batch_size % max(batch_shards, 1) == 0 else float("inf")
    prefer_batch_only = per_dev_batch_only <= seq_shard_budget

    def spec_of(path: str, s: jax.ShapeDtypeStruct):
        shp = s.shape[1:]  # strip the stacked periods dim
        if path.endswith("/pos"):
            return P()
        if path.endswith("/k") or path.endswith("/v"):
            if prefer_batch_only:
                base = _fit(mesh, shp,
                            P(BATCH, None, None, None),
                            P(BATCH, "model", None, None),
                            P(None, ("data", "model"), None, None),
                            P(None, "data", None, None))
            else:
                base = _fit(mesh, shp,
                            P(BATCH, "model", None, None),
                            P(BATCH, None, "model", None),
                            P(BATCH, None, None, None),
                            P(None, ("data", "model"), None, None),
                            P(None, "data", None, None))
        elif path.endswith("/h"):
            base = _fit(mesh, shp,
                        P(BATCH, "model", None, None),
                        P(BATCH, None, None, None),
                        P(None, "model", None, None))
        elif path.endswith("/conv"):
            base = _fit(mesh, shp,
                        P(BATCH, None, "model"),
                        P(BATCH, None, None),
                        P(None, None, "model"))
        else:
            base = P()
        return P(None, *base)

    def walk(tree, prefix=""):
        out = {}
        for k, v in tree.items():
            p = f"{prefix}/{k}"
            if isinstance(v, dict):
                out[k] = walk(v, p)
            else:
                out[k] = jax.ShapeDtypeStruct(
                    v.shape, v.dtype,
                    sharding=NamedSharding(mesh, spec_of(p, v)))
        return out

    return walk(shapes)


# ---------------------------------------------------------------------------
# train-state specs
# ---------------------------------------------------------------------------

def state_specs(model: TransformerLM, optimizer, mesh: Mesh, *,
                inference: bool = False):
    """Abstract TrainState with param/opt-state shardings from the rules.

    ``inference=True`` uses the serving layout (FSDP dim folded into TP —
    see sharding/rules.py:inference_spec) so decode never all-gathers
    weights per token.
    """
    from repro.core.fedlite import TrainState
    from repro.sharding.rules import inference_param_specs

    params_s = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt_s = jax.eval_shape(optimizer.init, params_s)

    def apply_specs(tree):
        specs = (inference_param_specs(tree, mesh) if inference
                 else param_specs(tree, mesh))
        return jax.tree.map(
            lambda s, sp: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, sp)),
            tree, specs,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

    params_s = apply_specs(params_s)
    opt_s = apply_specs(opt_s)
    step_s = jax.ShapeDtypeStruct((), jnp.int32,
                                  sharding=NamedSharding(mesh, P()))
    return TrainState(params=params_s, opt_state=opt_s, step=step_s)
