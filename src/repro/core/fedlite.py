"""FedLite / SplitFed / FedAvg training steps and communication accounting.

One jitted ``train_step`` realizes a full FedLite iteration (paper Fig. 1):

  client forward  ->  grouped PQ with gradient-corrected VJP  ->  server
  forward/backward  ->  client backward (receives the corrected activation
  cotangent)  ->  simultaneous client+server optimizer updates.

SplitFed is the ``quantize=False`` special case — by §3 of the paper it is
*exactly* mini-batch SGD, which ``tests/test_fedlite.py`` asserts bitwise.

The simulation maps each data-parallel mesh shard to a client cohort; the
bits that would cross the real client->server WAN link are accounted
analytically by ``comm_report`` (the paper's §3/§5 cost model), because the
whole point of the method is what it *saves on the uplink*, not what moves
across ICI inside the simulation.
"""

from __future__ import annotations

import dataclasses
import operator
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import kmeans as _km
from repro.core.quantizer import PQConfig
from repro.core.split import dtype_bits, tree_bits
from repro.models.transformer import TransformerLM
from repro.optim import Optimizer

# The steps' ``jax.named_scope``s (``fl_client`` ... ``fl_optimizer``) live
# in their ops' metadata, which is what a profiler trace reports. JAX leaves
# that metadata out of its persistent compilation cache's key by default, so
# a step compiled by another version of this code, with other scopes or
# none, would come back from the cache carrying that version's names.
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array

    @classmethod
    def create(cls, params, optimizer: Optimizer) -> "TrainState":
        return cls(params=params, opt_state=optimizer.init(params),
                   step=jnp.zeros((), jnp.int32))


def make_train_step(model: TransformerLM, optimizer: Optimizer, *,
                    quantize: bool = True,
                    microbatches: int = 1,
                    lam_schedule: Optional[Callable] = None,
                    donate: bool = True,
                    step_key: Optional[jax.Array] = None) -> Callable:
    """Build the jitted FedLite (quantize=True) / SplitFed (False) step.

    ``microbatches > 1`` runs gradient accumulation inside the step: the
    global batch is split along its leading axis into m sequential
    microbatches (a lax.scan), dividing peak activation memory by ~m at the
    same global batch size and numerics (grads averaged before the single
    optimizer update). Used by the memory-bound giant archs (see configs).

    ``lam_schedule(step) -> λ`` (beyond-paper): schedules the gradient-
    correction strength per step without recompilation — e.g. a warm-up that
    keeps λ≈0 until the server head carries signal, avoiding the
    activation-collapse failure mode of a strong constant λ at extreme
    compression (see EXPERIMENTS.md §Perf).

    ``step_key`` (beyond-paper): a base PRNG key; each step folds in
    ``state.step`` and hands the derived key to the model's cut-layer
    codecs — today that enables stochastic rounding on the ``scalarq``
    downlink. ``None`` keeps the deterministic, bitwise-historical path.

    The returned step accepts an optional third argument ``cut_state``
    (`core/compressors.CutState`): when passed, the model threads codebook
    warm-start / error-feedback state through the round and returns the
    updated state under ``metrics["cut_state"]`` (callers pop it before
    treating metrics as scalars). Incompatible with ``microbatches > 1``.
    """

    def grads_of(params, batch, step, cut_state=None):
        lam = None if lam_schedule is None else lam_schedule(step)
        key = None if step_key is None else jax.random.fold_in(step_key, step)
        return jax.value_and_grad(model.loss, has_aux=True)(
            params, batch, quantize=quantize, lam_override=lam, key=key,
            cut_state=cut_state)

    def train_step(state: TrainState, batch,
                   cut_state=None) -> Tuple[TrainState, Dict]:
        if microbatches == 1:
            (loss, metrics), grads = grads_of(state.params, batch, state.step,
                                              cut_state)
        else:
            if cut_state is not None:
                raise ValueError(
                    "cut_state is not supported with microbatches > 1")
            def split(x):
                return x.reshape((microbatches, x.shape[0] // microbatches)
                                 + x.shape[1:])

            mb = jax.tree.map(split, batch)
            zero_g = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)

            def acc(carry, mbatch):
                g_acc, loss_acc = carry
                (loss, metrics), g = grads_of(state.params, mbatch, state.step)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                return (g_acc, loss_acc + loss), metrics

            (g_sum, loss_sum), metrics = jax.lax.scan(
                acc, (zero_g, jnp.zeros((), jnp.float32)), mb)
            grads = jax.tree.map(
                lambda g, p: (g / microbatches).astype(p.dtype),
                g_sum, state.params)
            loss = loss_sum / microbatches
            metrics = jax.tree.map(lambda m: m[-1], metrics)

        with jax.named_scope("fl_optimizer"):
            updates, opt_state = optimizer.update(grads, state.opt_state,
                                                  state.params)
            params = jax.tree.map(operator.add, state.params, updates)
        metrics = dict(metrics, loss=loss)
        return TrainState(params, opt_state, state.step + 1), metrics

    return jax.jit(train_step, donate_argnums=(0,) if donate else ())


def make_weighted_step(model, optimizer: Optimizer, *,
                       quantize: bool = True, donate: bool = True,
                       step_key: Optional[jax.Array] = None) -> Callable:
    """Per-contribution staleness-weighted server update (FedBuff, exact).

    ``step(state, batches, weights)`` takes client-major batches (every leaf
    (C, B, ...)) and a (C,) weight vector; each client's gradient split is
    computed separately (vmap over the client axis) and discounted by ITS
    OWN staleness weight before aggregation:

        ĝ = (1/C) Σ_c w_c · g_c          (Nguyen et al. 2022, eq. 4)

    — where the cohort-level approximation the scheduler previously used
    scaled the fused cohort gradient by mean(w). The two agree exactly only
    when all buffered contributions share one staleness. Weights are traced
    (no recompile per staleness multiset); one optimizer update per flush.

    ``donate=True`` donates the train state to the jit — like
    ``make_train_step`` — so the optimizer update reuses the parameter
    buffers instead of copying the full params per async flush (pass False
    when the caller keeps using the pre-step state). ``step_key`` and the
    optional ``cut_state`` argument (leaves with a leading client axis)
    mirror ``make_train_step``'s cut-layer threading, per client.
    """

    def weighted_step(state: TrainState, batches, weights,
                      cut_state=None) -> Tuple[TrainState, Dict]:
        num_clients = weights.shape[0]
        base = None if step_key is None \
            else jax.random.fold_in(step_key, state.step)
        keys = None if base is None else jax.random.split(base, num_clients)

        def per_client(params, b, key, cs):
            (loss, metrics), g = jax.value_and_grad(
                model.loss, has_aux=True)(params, b, quantize=quantize,
                                          key=key, cut_state=cs)
            return g, loss, metrics

        grads, losses, metrics = jax.vmap(
            per_client,
            in_axes=(None, 0, None if keys is None else 0,
                     None if cut_state is None else 0))(
            state.params, batches, keys, cut_state)
        w = weights.astype(jnp.float32) / weights.shape[0]
        ghat = jax.tree.map(
            lambda g: jnp.tensordot(w, g.astype(jnp.float32), axes=1)
            .astype(g.dtype), grads)
        with jax.named_scope("fl_optimizer"):
            updates, opt_state = optimizer.update(ghat, state.opt_state,
                                                  state.params)
            params = jax.tree.map(operator.add, state.params, updates)
        # the cut state is carry, not a scalar metric: keep its client axis
        new_cut = metrics.pop("cut_state", None)
        metrics = jax.tree.map(lambda m: jnp.mean(m, axis=0), metrics)
        metrics = dict(metrics, loss=jnp.mean(losses),
                       mean_staleness_weight=jnp.mean(weights))
        if new_cut is not None:
            metrics["cut_state"] = new_cut
        return TrainState(params, opt_state, state.step + 1), metrics

    return jax.jit(weighted_step, donate_argnums=(0,) if donate else ())


def make_mesh_step(model, optimizer: Optimizer, mesh, *,
                   quantize: bool = True, donate: bool = True,
                   step_key: Optional[jax.Array] = None,
                   correction_scope: str = "cohort") -> Callable:
    """Cohort-parallel server update: shard_map over the ``clients`` axis.

    The mesh analogue of the stacked steps: client-major inputs (every
    batch leaf ``(C, B, ...)``, weights/mask ``(C,)``, optional per-client
    ``cut_state``) are sharded over ``mesh``'s ``clients`` axis; each shard
    computes its local clients' gradients (vmap, the per-client math of
    ``make_weighted_step`` — including the shard-local cut-state carry) and
    the weighted gradient sum crosses shards exactly once, as an explicit
    psum over ``clients``.

    ``mask`` (0/1 per client slot) exists because a cohort rarely divides
    the shard count: callers pad the client axis to a multiple of the mesh
    size and zero-mask the padding, which contributes nothing to the
    gradient or the masked metric means (padded slots' gradients are
    multiplied by the mask AFTER the cut hooks run, so the λ-correction of
    a duplicated padding row cannot leak either).

    ``correction_scope`` pins which stacked semantic the per-client
    gradients reproduce — the two differ ONLY in how FedLite's eq.-5
    λ-correction meets the loss scaling, because the correction is added to
    the raw activation cotangent inside the VJP hook rather than scaling
    with it:

      * ``"cohort"`` — the fused synchronous step (``make_train_step`` on
        the concatenated cohort batch): each client's loss is pre-scaled by
        ``w_c / Σm`` INSIDE differentiation, so the data cotangent reaching
        the cut hook carries the global 1/(C·B) scale while the correction
        fires at full λ — gradients match the stacked step bit-for-bit up
        to float reassociation. Used by the synchronous policies.
      * ``"client"`` — ``make_weighted_step`` (FedBuff): raw per-client
        gradients (correction at λ against the client-local 1/B cotangent)
        are discounted AFTER differentiation by ``w_c / Σm``. Used under
        `AsyncBuffer`, where the staleness weights must discount the whole
        contribution, correction included.

    Per-client metrics come back masked-mean-reduced; the cut state (when
    passed) returns under ``metrics["cut_state"]`` in client-major layout,
    sharding preserved, padding slots still attached (callers absorb only
    the unmasked entries). One optimizer update per call, on the replicated
    combined gradient — parameters never shard over ``clients``.
    """
    from jax.sharding import PartitionSpec as P

    from repro.sharding.ctx import CLIENTS_AXIS

    if CLIENTS_AXIS not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no "
                         f"{CLIENTS_AXIS!r} axis")
    if correction_scope not in ("cohort", "client"):
        raise ValueError(f"correction_scope={correction_scope!r} must be "
                         "'cohort' or 'client'")
    pre_scale = correction_scope == "cohort"

    def mesh_step(state: TrainState, batches, weights, mask,
                  cut_state=None) -> Tuple[TrainState, Dict]:
        num_slots = weights.shape[0]
        base = None if step_key is None \
            else jax.random.fold_in(step_key, state.step)
        keys = None if base is None else jax.random.split(base, num_slots)
        cnt = jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)

        def shard_local(params, b, w, m, keys_l, cs):
            def per_client(b_i, s_i, key_i, cs_i):
                def scaled(p):
                    loss, metrics = model.loss(p, b_i, quantize=quantize,
                                               key=key_i, cut_state=cs_i)
                    return loss * (s_i if pre_scale else 1.0), (loss, metrics)

                (_, (loss, metrics)), g = jax.value_and_grad(
                    scaled, has_aux=True)(params)
                return g, loss, metrics

            scale = (w / cnt).astype(jnp.float32)
            grads, losses, metrics = jax.vmap(
                per_client,
                in_axes=(0, 0, None if keys_l is None else 0,
                         None if cs is None else 0))(b, scale, keys_l, cs)
            # padding slots are zeroed AFTER differentiation either way (the
            # λ-correction inside the hook does not scale with the loss);
            # "client" scope additionally applies the weight here
            post = (m if pre_scale else m * scale).astype(jnp.float32)
            gsum = jax.tree.map(
                lambda g: jax.lax.psum(
                    jnp.tensordot(post, g.astype(jnp.float32), axes=1),
                    CLIENTS_AXIS), grads)
            return gsum, losses, metrics

        # prefix specs: every client-major pytree (batches, keys, cut state,
        # per-client losses/metrics) shards its LEADING axis over `clients`;
        # params and the psum'd gradient stay replicated
        gsum, losses, metrics = jax.shard_map(
            shard_local, mesh=mesh,
            in_specs=(P(), P(CLIENTS_AXIS), P(CLIENTS_AXIS), P(CLIENTS_AXIS),
                      P(CLIENTS_AXIS), P(CLIENTS_AXIS)),
            out_specs=(P(), P(CLIENTS_AXIS), P(CLIENTS_AXIS)),
            check_vma=False)(state.params, batches, weights, mask, keys,
                             cut_state)
        ghat = jax.tree.map(
            lambda g, p: g.astype(p.dtype), gsum, state.params)
        with jax.named_scope("fl_optimizer"):
            updates, opt_state = optimizer.update(ghat, state.opt_state,
                                                  state.params)
            params = jax.tree.map(operator.add, state.params, updates)
        new_cut = metrics.pop("cut_state", None)
        mf = mask.astype(jnp.float32)
        metrics = jax.tree.map(lambda x: jnp.sum(x * mf) / cnt, metrics)
        metrics = dict(
            metrics, loss=jnp.sum(losses * mf) / cnt,
            mean_staleness_weight=jnp.sum(weights * mf) / cnt)
        if new_cut is not None:
            metrics["cut_state"] = new_cut
        return TrainState(params, opt_state, state.step + 1), metrics

    return jax.jit(mesh_step, donate_argnums=(0,) if donate else ())


def make_eval_step(model: TransformerLM) -> Callable:
    def eval_step(params, batch):
        acts, _, _ = model.client_forward(params["client"], batch, mode="train")
        x, _, _ = model.server_forward(params["server"], acts, batch,
                                       mode="train")
        lg = model.logits(params, x)
        ce = model.token_ce(lg, batch["labels"])
        pred = jnp.argmax(lg, axis=-1)
        labels = batch["labels"]
        if model.cfg.num_codebooks > 1:
            labels = jnp.moveaxis(labels, 1, 2)
        mask = labels >= 0
        acc = jnp.sum((pred == labels) * mask) / jnp.maximum(mask.sum(), 1)
        return {"ce": ce, "accuracy": acc}

    return jax.jit(eval_step)


# ---------------------------------------------------------------------------
# communication accounting (paper Table 1 + §5 worked example)
# ---------------------------------------------------------------------------

def comm_report(model: TransformerLM, params, tokens_per_client: int,
                pq: Optional[PQConfig] = None,
                phi_bits: Optional[int] = None) -> Dict[str, float]:
    """Per-client, per-iteration wire bits for FedAvg / SplitFed / FedLite.

    ``tokens_per_client`` is B (examples per client) × activation vectors per
    example (seq length for LMs; 1 for the paper's CNN whose cut activation
    is a single flattened vector).

    ``phi_bits=None`` (default) derives the accounting width from the actual
    dtypes: parameters count per-leaf dtype bits, activations (and the PQ
    codebooks) count the model's compute dtype. Pass φ=64 explicitly to
    reproduce the paper's fixed-width §5 numbers.

    Downlink: the cut-layer gradient message is the same B·d floats unless
    the model carries a ``downlink_compressor``, in which case its analytic
    bits are reported alongside the dense baseline.
    """
    d = model.cfg.d_model
    pq = pq if pq is not None else model.pq
    act_phi = phi_bits if phi_bits is not None else \
        dtype_bits(getattr(model.cfg, "dtype", "float32"))
    client_bits = tree_bits(params["client"], phi_bits)
    total_bits = client_bits + tree_bits(params["server"], phi_bits)
    act_bits = act_phi * d * tokens_per_client

    report = {
        "activation_dim": d,
        "tokens_per_client": tokens_per_client,
        "phi_bits": float(act_phi),
        "pq_backend": None if pq is None else _km.resolve_backend(pq.backend),
        "fedavg_uplink_bits": float(total_bits),
        "splitfed_uplink_bits": float(client_bits + act_bits),
        "splitfed_activation_bits": float(act_bits),
        "downlink_dense_bits": float(act_bits),
    }
    if pq is not None:
        msg = pq.message_bits(tokens_per_client, d, phi_bits=act_phi)
        report.update({
            "fedlite_uplink_bits": float(client_bits + msg),
            "fedlite_activation_bits": float(msg),
            "activation_compression_ratio": act_bits / max(msg, 1),
            "uplink_reduction_vs_splitfed":
                (client_bits + act_bits) / max(client_bits + msg, 1),
            "uplink_reduction_vs_fedavg":
                total_bits / max(client_bits + msg, 1),
        })
    dl = getattr(model, "downlink_compressor", None)
    if dl is not None and dl.name != "none":
        dl_bits = dl.analytic_bits(tokens_per_client, d, phi_bits=act_phi)
        report.update({
            "downlink_compressor": getattr(dl, "spec", dl.name),
            "downlink_bits": float(dl_bits),
            "downlink_compression_ratio": act_bits / max(dl_bits, 1),
        })
    return report
