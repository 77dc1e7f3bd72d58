"""Unified decoder LM covering all six assigned families.

A model is a repeated *period* of blocks (``cfg.layer_pattern``): pure dense
archs have period ("attn",); jamba has an 8-block mamba/attention interleave;
MoE FFNs replace dense FFNs on layers selected by (moe_period, moe_offset),
except on ``first_dense_layers`` leading layers (DeepSeek-V3's dense first
layer), which are the client's whole stack when set.
Weights for each position in the period are stacked over periods and the
period is applied under ``lax.scan`` (+ per-period remat for training), so
HLO size and compile time are independent of depth.

FedLite split: ``params = {"client": ..., "server": ...}``. The client owns
the embedding (+ modality projector) and the first ``cfg.cut_periods``
periods; the server owns the rest, the final norm and the (frequently
enormous — 256k vocab) LM head, exactly the paper's resource-constrained
regime. ``client_forward`` emits the cut-layer activation that FedLite
quantizes.

Modality carve-out (per assignment): VLM vision towers and audio codecs are
stubs — batches carry precomputed ``vision_embeds`` (projected here) or
multi-codebook token grids; this module implements only the decoder backbone.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.compressors import (CutCompressor, CutState, PQCompressor,
                                    apply_cut)
from repro.core.quantizer import PQConfig
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (apply_mlp, apply_norm, embed_init, dense_init,
                                 mlp_init, norm_init)
from repro.sharding import shard, shard_residual

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class TransformerLM:
    cfg: ArchConfig
    pq: Optional[PQConfig] = None     # FedLite quantizer at the cut layer
    lam: float = 0.0                  # gradient-correction strength (eq. 5)
    # direction-agnostic cut-layer codecs (core/compressors.py):
    # uplink_compressor replaces PQCompressor(pq) when set; the downlink
    # compressor squeezes the server->client gradient COTANGENT in the VJP
    uplink_compressor: Optional[CutCompressor] = None
    downlink_compressor: Optional[CutCompressor] = None

    # ------------------------------------------------------------------ init
    def init(self, key: jax.Array) -> Params:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.param_dtype)
        k_embed, k_client, k_server, k_head, k_vis = jax.random.split(key, 5)

        client: Params = {}
        if cfg.num_codebooks > 1:
            client["tok_embed"] = jnp.stack([
                embed_init(k, cfg.padded_vocab, cfg.d_model, dtype)
                for k in jax.random.split(k_embed, cfg.num_codebooks)])
        else:
            client["tok_embed"] = embed_init(k_embed, cfg.padded_vocab,
                                             cfg.d_model, dtype)
        if cfg.vision_embed_dim:
            client["vision_proj"] = dense_init(k_vis, cfg.vision_embed_dim,
                                               cfg.d_model, dtype)
        client["layers"] = self._init_stack(k_client, cfg.cut_periods, dtype,
                                            server=False)

        server: Params = {
            "layers": self._init_stack(
                k_server, cfg.num_periods - cfg.cut_periods, dtype,
                server=True),
            "final_norm": norm_init(cfg.d_model, cfg.norm_type, dtype),
        }
        if not cfg.tie_embeddings:
            if cfg.num_codebooks > 1:
                server["head"] = jnp.stack([
                    dense_init(k, cfg.d_model, cfg.padded_vocab, dtype)
                    for k in jax.random.split(k_head, cfg.num_codebooks)])
            else:
                server["head"] = dense_init(k_head, cfg.d_model,
                                            cfg.padded_vocab, dtype)
        return {"client": client, "server": server}

    def _init_stack(self, key, n_periods: int, dtype, *, server: bool
                    ) -> Params:
        cfg = self.cfg

        def init_period(k):
            p = {}
            ks = jax.random.split(k, cfg.period)
            for pos in range(cfg.period):
                kk = jax.random.split(ks[pos], 3)
                kind = cfg.layer_pattern[pos]
                lp = {"ln1": norm_init(cfg.d_model, cfg.norm_type, dtype),
                      "ln2": norm_init(cfg.d_model, cfg.norm_type, dtype)}
                if kind == "attn":
                    lp["mixer"] = attn_mod.attn_init(kk[0], cfg, dtype)
                else:
                    lp["mixer"] = ssm_mod.ssm_init(kk[0], cfg, dtype)
                if self._pos_is_moe(pos, server):
                    init = moe_mod.dropless_init \
                        if cfg.moe_layer == "dropless" else moe_mod.moe_init
                    lp["ffn"] = init(kk[1], cfg, dtype)
                elif cfg.d_ff:
                    lp["ffn"] = mlp_init(kk[1], cfg.d_model, cfg.d_ff,
                                         cfg.mlp_type, cfg.use_bias, dtype)
                p[f"p{pos}"] = lp
            return p

        keys = jax.random.split(key, max(n_periods, 1))[:n_periods]
        periods = [init_period(k) for k in keys]
        if not periods:
            return {}
        return jax.tree.map(lambda *xs: jnp.stack(xs), *periods)

    def _pos_is_moe(self, pos: int, server: bool) -> bool:
        # valid because period % moe_period == 0 and the cut offset is a whole
        # number of periods, so the flag is position-static across the scan;
        # leading dense layers are the client's whole stack (ArchConfig)
        return bool(self.cfg.num_experts) and \
            (server or not self.cfg.first_dense_layers) and \
            (pos % self.cfg.moe_period == self.cfg.moe_offset)

    # ----------------------------------------------------------- embeddings
    def embed(self, client_params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        cfg = self.cfg
        emb = client_params["tok_embed"]
        tokens = batch["tokens"]
        if cfg.num_codebooks > 1:       # audio: (B, K, S) token grid
            x = sum(jnp.take(emb[k], tokens[:, k], axis=0)
                    for k in range(cfg.num_codebooks))
        else:
            x = jnp.take(emb, tokens, axis=0)
        if cfg.scale_embed:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        if cfg.vision_embed_dim and "vision_embeds" in batch:
            vis = batch["vision_embeds"].astype(x.dtype) @ client_params["vision_proj"]
            x = jnp.concatenate([vis, x], axis=1)
        x = x.astype(cfg.compute_dtype)
        return shard_residual(x)

    # ------------------------------------------------------------- periods
    def _apply_period(self, pp: Params, x, positions, mode, caches, decode_pos,
                      server: bool):
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        new_caches = {} if caches is not None else None
        # nested remat: with multi-block periods (jamba's 8), rematerializing
        # the whole period at once would hold every block's internals (SSD
        # chunk stacks, MoE buffers) live simultaneously during the backward
        # pass — per-block checkpoints keep only one block's internals alive
        inner_ckpt = (mode == "train" and cfg.remat and cfg.period > 1)

        def maybe_ckpt(fn):
            return jax.checkpoint(fn) if inner_ckpt else fn

        for pos in range(cfg.period):
            lp = pp[f"p{pos}"]
            kind = cfg.layer_pattern[pos]
            cache = caches[f"p{pos}"] if caches is not None else None

            if kind == "attn":
                def mixer_fn(lp_, x_, cache_):
                    h = apply_norm(lp_["ln1"], x_, cfg.norm_type, cfg.norm_eps)
                    return attn_mod.apply_attention(
                        lp_["mixer"], h, cfg, positions, mode=mode,
                        cache=cache_, decode_pos=decode_pos)
            else:
                def mixer_fn(lp_, x_, cache_):
                    h = apply_norm(lp_["ln1"], x_, cfg.norm_type, cfg.norm_eps)
                    return ssm_mod.apply_ssm(lp_["mixer"], h, cfg, mode=mode,
                                             cache=cache_)
            y, new_c = maybe_ckpt(mixer_fn)(lp, x, cache)
            x = x + y
            if "ffn" in lp:
                if self._pos_is_moe(pos, server):
                    apply = moe_mod.apply_dropless_moe \
                        if cfg.moe_layer == "dropless" else moe_mod.apply_moe

                    def ffn_fn(lp_, x_):
                        h = apply_norm(lp_["ln2"], x_, cfg.norm_type,
                                       cfg.norm_eps)
                        return apply(lp_["ffn"], h, cfg)
                    y, a = maybe_ckpt(ffn_fn)(lp, x)
                    aux = aux + a
                else:
                    def ffn_fn(lp_, x_):
                        h = apply_norm(lp_["ln2"], x_, cfg.norm_type,
                                       cfg.norm_eps)
                        return apply_mlp(lp_["ffn"], h, cfg.mlp_type)
                    y = maybe_ckpt(ffn_fn)(lp, x)
                x = x + y
            if new_caches is not None:
                new_caches[f"p{pos}"] = new_c
        return x, new_caches, aux

    def _run_stack(self, layers: Params, x, positions, mode, caches, decode_pos,
                   server: bool):
        """Scan the stacked periods. caches: stacked pytree or None."""
        if not layers:
            return x, caches, jnp.zeros((), jnp.float32)
        cfg = self.cfg

        has_caches = caches is not None

        def body(carry, xs):
            x, aux = carry
            pslice, cslice = xs
            x, new_c, a = self._apply_period(pslice, x, positions, mode,
                                             cslice if has_caches else None,
                                             decode_pos, server)
            return (x, aux + a), (new_c if has_caches else cslice)

        if cfg.remat and mode == "train":
            policy = None
            if cfg.remat_policy == "dots":
                # save matmul outputs across the period boundary: trades HBM
                # headroom for skipping most of the backward recompute pass
                policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            body = jax.checkpoint(body, policy=policy)

        n = jax.tree.leaves(layers)[0].shape[0]
        cs = caches if caches is not None else _none_like(layers, n)
        (x, aux), new_caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                            (layers, cs))
        if caches is None:
            new_caches = None
        return x, new_caches, aux

    # ------------------------------------------------------- fedlite split
    def client_forward(self, client_params: Params, batch, *, mode="train",
                       caches=None, decode_pos=None):
        """Embed + first cut_periods periods -> cut-layer activation."""
        x = self.embed(client_params, batch)
        positions = self._positions(batch, x.shape[1], decode_pos)
        x, new_caches, aux = self._run_stack(client_params["layers"], x,
                                             positions, mode, caches, decode_pos,
                                             server=False)
        return x, new_caches, aux

    def _cut(self, x: jax.Array, *, quantize: bool, lam_override=None,
             key: Optional[jax.Array] = None,
             cut_state: Optional[CutState] = None
             ) -> Tuple[jax.Array, Dict]:
        """The cut-layer codecs (``core/compressors.apply_cut``), one client
        per batch row (sequence): codebooks are built per row, matching the
        paper's per-client clustering, and the codecs need no collective
        over the batch-sharded mesh axis. ``cut_state`` leaves carry the
        leading row axis."""
        if not quantize:
            return x, {}
        up = self.uplink_compressor
        if up is None and self.pq is not None:
            up = PQCompressor(self.pq)
        lam = self.lam if lam_override is None else lam_override
        # gather each client's (sequence-sharded) activation so the
        # per-client compression runs locally, as a real client's does
        x = shard(x, ("pod", "data"), None, None)
        z, stats = apply_cut(x, up, self.downlink_compressor, lam, key=key,
                             state=cut_state)
        return shard_residual(z), stats

    def server_forward(self, server_params: Params, acts, batch, *, mode="train",
                       caches=None, decode_pos=None):
        positions = self._positions(batch, acts.shape[1], decode_pos)
        x, new_caches, aux = self._run_stack(server_params["layers"], acts,
                                             positions, mode, caches, decode_pos,
                                             server=True)
        x = apply_norm(server_params["final_norm"], x, self.cfg.norm_type,
                       self.cfg.norm_eps)
        return x, new_caches, aux

    def head_matrix(self, params: Params) -> jax.Array:
        """(D, Vp) LM head in column-parallel layout. For tied embeddings the
        (d_model-sharded) table is transposed and re-constrained HERE — once,
        outside the CE chunk scan — so the vocab-sharded layout is
        established before any (B, chunk, V) logits exist."""
        cfg = self.cfg
        if cfg.tie_embeddings:
            head = params["client"]["tok_embed"].T  # (D, Vp)
        else:
            head = params["server"]["head"]
        if cfg.num_codebooks > 1:
            return shard(head, None, "data", "model")
        return shard(head, "data", "model")

    def logits(self, params: Params, x: jax.Array,
               head: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.cfg
        head = head if head is not None else self.head_matrix(params)
        if cfg.num_codebooks > 1:
            out = jnp.einsum("bsd,kdv->bskv", x, head.astype(x.dtype))
        else:
            out = x @ head.astype(x.dtype)
        return shard(out.astype(jnp.float32), ("pod", "data"), None, "model")

    # ------------------------------------------------------------- losses
    def loss(self, params: Params, batch, *, quantize: bool = True,
             lam_override=None, key=None, cut_state=None):
        """Full FedLite forward: client -> PQ (+corrected VJP) -> server -> CE."""
        # named scopes attribute the step's device operations (and their
        # transposes) to the layers of a server update
        with jax.named_scope("fl_client"):
            acts, _, aux_c = self.client_forward(params["client"], batch,
                                                 mode="train")
        with jax.named_scope("fl_uplink_codec"):
            acts, pq_stats = self._cut(acts, quantize=quantize,
                                       lam_override=lam_override, key=key,
                                       cut_state=cut_state)
        with jax.named_scope("fl_server"):
            x, _, aux_s = self.server_forward(params["server"], acts, batch,
                                              mode="train")
            ce = self.chunked_ce(params, x, batch["labels"])
        metrics = {"ce": ce, "aux": aux_c + aux_s, **pq_stats}
        return ce + aux_c + aux_s, metrics

    def chunked_ce(self, params: Params, x: jax.Array, labels: jax.Array,
                   chunk: int = 512) -> jax.Array:
        """CE without materializing full (B, S, V) logits: scan over sequence
        chunks, rematerializing each chunk's logits in the backward pass —
        peak logits memory drops from S/chunk× to 1×."""
        cfg = self.cfg
        if cfg.num_codebooks > 1:
            labels = jnp.moveaxis(labels, 1, 2)          # (B,S,K)
        B, S = x.shape[:2]
        if S % chunk != 0 or S <= chunk:
            lg = self.logits(params, x)
            return self._ce_sum(lg, labels) / jnp.maximum(
                jnp.sum(labels >= 0), 1)

        nc = S // chunk
        xc = x.reshape(B, nc, chunk, -1).transpose(1, 0, 2, 3)
        lc = labels.reshape((B, nc, chunk) + labels.shape[2:])
        lc = jnp.moveaxis(lc, 1, 0)
        head = self.head_matrix(params)   # resharded once, outside the scan

        @jax.checkpoint
        def body(carry, inp):
            xb, lb = inp
            lg = self.logits(params, xb, head=head)
            return carry + self._ce_sum(lg, lb), None

        tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xc, lc))
        return tot / jnp.maximum(jnp.sum(labels >= 0), 1)

    def _ce_sum(self, logits: jax.Array, labels: jax.Array) -> jax.Array:
        """Sum of masked token CE. labels already (B,S[,K])-shaped."""
        vocab_ok = jnp.arange(logits.shape[-1]) < self.cfg.vocab_size
        logits = jnp.where(vocab_ok, logits, -1e30)
        mask = labels >= 0
        safe = jnp.maximum(labels, 0)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - picked) * mask)

    def token_ce(self, logits: jax.Array, labels: jax.Array) -> jax.Array:
        if self.cfg.num_codebooks > 1:   # (B,S,K,V) vs (B,K,S)
            labels = jnp.moveaxis(labels, 1, 2)  # (B,S,K)
        return self._ce_sum(logits, labels) / jnp.maximum(
            jnp.sum(labels >= 0), 1)

    # --------------------------------------------------------- inference
    def init_caches(self, batch_size: int, max_len: int) -> Params:
        cfg = self.cfg
        dtype = cfg.compute_dtype

        def stack_caches(n_periods):
            if n_periods == 0:
                return {}
            per = {}
            for pos in range(cfg.period):
                if cfg.layer_pattern[pos] == "attn":
                    c = attn_mod.init_attn_cache(cfg, batch_size, max_len, dtype)
                else:
                    c = ssm_mod.init_ssm_cache(cfg, batch_size, dtype)
                per[f"p{pos}"] = c
            return jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n_periods,) + x.shape), per)

        return {"client": stack_caches(cfg.cut_periods),
                "server": stack_caches(cfg.num_periods - cfg.cut_periods)}

    def prefill(self, params: Params, batch, caches, *, quantize: bool = False):
        """Process the prompt, fill caches, return last-token logits.

        ``quantize=True`` compresses the cut-layer activation with the paper's
        PQ before it crosses the client->server link (split inference).
        """
        acts, c_caches, _ = self.client_forward(
            params["client"], batch, mode="prefill", caches=caches["client"])
        acts, _ = self._cut(acts, quantize=quantize)
        x, s_caches, _ = self.server_forward(
            params["server"], acts, batch, mode="prefill", caches=caches["server"])
        lg = self.logits(params, x[:, -1:])
        return lg, {"client": c_caches, "server": s_caches}

    def decode_step(self, params: Params, caches, tokens, decode_pos):
        """One token (B,1) / (B,K,1) at absolute position ``decode_pos``."""
        batch = {"tokens": tokens}
        acts, c_caches, _ = self.client_forward(
            params["client"], batch, mode="decode", caches=caches["client"],
            decode_pos=decode_pos)
        x, s_caches, _ = self.server_forward(
            params["server"], acts, batch, mode="decode",
            caches=caches["server"], decode_pos=decode_pos)
        lg = self.logits(params, x)
        return lg, {"client": c_caches, "server": s_caches}

    # ------------------------------------------------------------- helpers
    def _positions(self, batch, seq_len: int, decode_pos):
        cfg = self.cfg
        if "positions" in batch:
            return batch["positions"]
        B = batch["tokens"].shape[0]
        if decode_pos is not None:
            pos = jnp.full((B, 1), decode_pos, jnp.int32)
        else:
            pos = jnp.broadcast_to(jnp.arange(seq_len, dtype=jnp.int32), (B, seq_len))
        if cfg.mrope_sections is not None:
            pos = jnp.broadcast_to(pos, (3,) + pos.shape)
        return pos


def _none_like(layers: Params, n: int):
    """A scannable placeholder cache (zero-size) when no caches are used."""
    return jnp.zeros((n, 0), jnp.float32)
