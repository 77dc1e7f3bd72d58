"""Federated runtime: client sampling, weighted aggregation, round drivers.

Implements the three algorithms the paper compares (§3, Table 1):

  * FEDAVG      — every sampled client runs H local SGD steps on the FULL
                  model, the server averages the deltas weighted by p_i.
  * SPLITFED    — per iteration, the cohort's activations hit the server,
                  gradients come back; equivalent to mini-batch SGD (§3).
  * FEDLITE     — SplitFed + grouped PQ + gradient correction at the cut.

SplitFed/FedLite iterations are realized by a single jitted train step over
the cohort's combined batch (see ``core/fedlite.py``) — mathematically
identical to per-client messaging with p_i-weighted server aggregation when
client batches are equal-sized, and exactly what the production mesh runs
(each data shard = one cohort). FedAvg keeps the explicit per-client local
loop since its local-step structure cannot be fused.

`FederatedTrainer.run` drives rounds through the virtual-clock
``federated/scheduler.py``: the default fleet/policy (identical
infinitely-fast clients, full sync) bitwise-reproduces the original
synchronous loop, while heterogeneous fleets + straggler policies turn the
same trainer into a measurement harness — per-round simulated wall-clock
and *measured* wire bytes (``federated/wire.py``) land in
``trainer.last_trace``.
"""

from __future__ import annotations

import dataclasses
import logging
import operator
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.compressors import (CutCompressor, CutState, NoneCompressor,
                                    PQCompressor, make_compressor)
from repro.core.fedlite import TrainState
from repro.core.quantizer import QuantizerState, quantize_stateful
from repro.data.synthetic import FederatedDataset
from repro.federated import wire
from repro.federated.executor import make_executor
from repro.federated.faults import FaultPlan, make_injector
from repro.federated.network import ClientProfile, uniform_fleet, validate_fleet
from repro.federated.scheduler import (Arrival, AsyncBuffer, FullSync,
                                       Policy, Scheduler)
from repro.federated.trace import Trace
from repro.obs import flight as flightlib
from repro.optim import Optimizer

logger = logging.getLogger(__name__)


def sample_clients(rng: np.random.Generator, num_clients: int, cohort: int,
                   weights: Optional[np.ndarray] = None) -> np.ndarray:
    """Sample a cohort without replacement, uniformly or p_i-proportionally.

    ``weights`` (e.g. ``FederatedDataset.client_weights``, p_i ∝ n_i) biases
    selection toward data-rich clients — the sampling the FedAvg analysis
    assumes; ``None`` keeps the uniform sampling SplitFed/FedLite use.
    """
    size = min(cohort, num_clients)
    if weights is None:
        return rng.choice(num_clients, size=size, replace=False)
    p = np.asarray(weights, np.float64)
    if p.shape != (num_clients,) or (p < 0).any() or p.sum() <= 0:
        raise ValueError("weights must be a nonnegative (num_clients,) vector")
    return rng.choice(num_clients, size=size, replace=False, p=p / p.sum())


def weighted_average(trees: Sequence[Any], weights: Sequence[float]):
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    return jax.tree.map(
        lambda *xs: sum(wi * x for wi, x in zip(w, xs)), *trees)


# ---------------------------------------------------------------------------
# FedAvg baseline
# ---------------------------------------------------------------------------

def make_fedavg_step(model, lr: float):
    """The jitted single local SGD step (client batch sampled outside jit).

    Built ONCE per (model, lr) and reused across every round — a jit
    closure rebuilt inside the round function would retrace per round."""
    @jax.jit
    def sgd_step(p, b):
        loss, grads = jax.value_and_grad(
            lambda q: model.loss(q, b, quantize=False)[0])(p)
        new_p = jax.tree.map(lambda x, g: x - lr * g, p, grads)
        return new_p, loss
    return sgd_step


def fedavg_round(model, params, data: FederatedDataset, client_ids,
                 key: jax.Array, *, local_steps: int, batch: int,
                 lr: float, batch_kwargs: Optional[dict] = None,
                 sgd_step=None):
    """One FedAvg round: H local SGD steps per client, weighted delta average.

    Returns (new_params, mean local loss). Local updates are plain SGD as in
    McMahan et al. (2017). ``sgd_step`` (from `make_fedavg_step`) lets the
    round driver reuse one jit cache across rounds. The mean loss is
    returned as a DEVICE scalar — no host sync per round; the caller
    batches the transfer (``run_fedavg`` flushes every round's loss through
    one `obs.MetricsBuffer` transfer at the end of the run).
    """
    batch_kwargs = batch_kwargs or {}
    if sgd_step is None:
        sgd_step = make_fedavg_step(model, lr)

    deltas, losses = [], []
    for cid in client_ids:
        p = params
        ck = jax.random.fold_in(key, int(cid))
        for s in range(local_steps):
            b = data.sample_batch(int(cid), jax.random.fold_in(ck, s), batch,
                                  **batch_kwargs)
            p, loss = sgd_step(p, b)
            losses.append(loss)
        deltas.append(jax.tree.map(operator.sub, p, params))
    weights = [float(data.client_weights[int(cid)]) for cid in client_ids]

    mean_delta = weighted_average(deltas, weights)
    new_params = jax.tree.map(operator.add, params, mean_delta)
    return new_params, jnp.mean(jnp.stack(losses))


def run_fedavg(model, params, data: FederatedDataset, *, rounds: int,
               cohort: int, key: jax.Array, local_steps: int, batch: int,
               lr: float, weighted_sampling: bool = True, seed: int = 0,
               batch_kwargs: Optional[dict] = None):
    """FedAvg driver: p_i-proportional cohort sampling + weighted averaging.

    Returns (params, per-round mean-loss list)."""
    rng = np.random.default_rng(seed)
    weights = data.client_weights if weighted_sampling else None
    sgd_step = make_fedavg_step(model, lr)   # one jit cache for the run
    buf = obs.MetricsBuffer()   # device losses; one transfer at end of run
    for r in range(rounds):
        ids = sample_clients(rng, data.num_clients, cohort, weights=weights)
        params, loss = fedavg_round(
            model, params, data, ids, jax.random.fold_in(key, r + 1),
            local_steps=local_steps, batch=batch, lr=lr,
            batch_kwargs=batch_kwargs, sgd_step=sgd_step)
        buf.record({"loss": loss})
    return params, [m["loss"] for m in buf.flush()]


# ---------------------------------------------------------------------------
# SplitFed / FedLite trainer
# ---------------------------------------------------------------------------

@jax.jit
def _cohort_keys(round_key: jax.Array, cids: jax.Array,
                 versions: Optional[jax.Array] = None):
    """Every participant's PRNG key in one program.

    Key ``i`` is ``fold_in(round_key, cids[i])``, or, with ``versions``,
    ``fold_in(fold_in(round_key, versions[i] + 1), cids[i])`` (the scheduled
    run's key of the model version the client trained against): bitwise
    what the per-client eager ``fold_in``s give. Returns a tuple of keys,
    one dispatch with N outputs: indexing one (N, 2) array on the host
    would be N eager ops again. Retraces per cohort size."""
    fold = jax.vmap(jax.random.fold_in, in_axes=(None, 0))
    keys = fold(round_key, cids) if versions is None \
        else jax.vmap(jax.random.fold_in)(fold(round_key, versions + 1), cids)
    return tuple(keys)


@jax.jit
def _concat_cohort(parts):
    """The cohort's client batches joined along axis 0, every leaf in one
    program (retraces per cohort size and tree structure)."""
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *parts)


@dataclasses.dataclass
class FederatedTrainer:
    """Round driver for split-learning algorithms on a FederatedDataset.

    Each round samples a cohort, stacks the cohort's client batches into one
    global batch (cohort = leading batch dim) and runs the jitted split step.

    Rounds are dispatched by the virtual-clock `Scheduler`: ``fleet`` (one
    `ClientProfile` per client; default identical ideal clients) and
    ``policy`` (default `FullSync`) select the heterogeneity scenario. With
    the defaults the trajectory is bitwise-identical to a plain
    ``round()``-by-``round()`` loop; under straggler policies the stacked
    batch shrinks to the survivors (one extra jit cache entry per distinct
    survivor count). ``run`` leaves the per-round `Trace` — simulated
    wall-clock + measured wire bytes — in ``self.last_trace``.

    WHERE each round's per-client math executes is the ``executor``'s job
    (``federated/executor.py``): the ``"stacked"`` default is the
    single-device path described above; ``"mesh"`` shards the cohort over
    the ``clients`` axis of a device mesh (per-client batches/PRNG
    keys/EF memories/`CutState`s placed with NamedSharding, shard-local
    gradients combined by one explicit psum). Policies, traces and the
    wire measurement are executor-agnostic; traces additionally record
    each participant's shard placement.
    """
    model: Any
    optimizer: Optimizer
    data: FederatedDataset
    cohort: int
    client_batch: int
    quantize: bool = True
    batch_kwargs: Optional[dict] = None
    seed: int = 0
    fleet: Optional[Sequence[ClientProfile]] = None
    policy: Optional[Policy] = None
    client_step_seconds: float = 1.0
    server_step_seconds: float = 0.0
    codebook_wire_dtype: str = "float16"
    # per-direction cut-layer codecs (spec string or CutCompressor; see
    # core/compressors.py). Uplink default: the model's PQ ("pq") or dense
    # ("none"). Downlink default: whatever the model carries, else dense.
    # A downlink spec is installed INTO the model (dataclasses.replace), so
    # the training VJP and the measured wire bytes use the same codec.
    uplink_compressor: Any = None
    downlink_compressor: Any = None
    # ---- cross-round cut-layer state (all default-off: bitwise-historical)
    # warm_start: carry the PQ codebooks across scheduler rounds — Lloyd
    # resumes from last round's codebook at PQConfig.warm_iters iterations
    # (cohort-global on the stacked/FullSync path; per-client under
    # AsyncBuffer, falling back to a cold round whenever the buffer holds a
    # first-time client).
    warm_start: bool = False
    # error_feedback: per-client uplink error-feedback memory (the
    # `ErrorFeedback` telescoping semantics), gathered/scattered by client
    # id across rounds — clients re-add their accumulated compression error
    # before compressing.
    error_feedback: bool = False
    # stochastic_downlink: thread a per-step PRNG key into the downlink
    # VJP so scalarq gradient codecs round stochastically (unbiased).
    stochastic_downlink: bool = False
    # codebook_delta_bits: measure the pq directions with the `pq-delta`
    # wire kind (quantized codebook deltas vs the acked reference) instead
    # of fresh fp16 codebooks; the measured steady-state bytes feed the
    # scheduler. Applies to the uplink AND — when the downlink codec is pq
    # — the downlink gradient message (PR 4's delta machinery covers both
    # directions).
    codebook_delta_bits: Optional[int] = None
    # executor: the cohort execution engine (federated/executor.py) that
    # maps each server update's per-client math onto devices — "stacked"
    # (single-device historical path, bitwise default), "mesh" /
    # "mesh(shards=N)" (shard_map over the `clients` device axis), or a
    # CohortExecutor instance.
    executor: Any = "stacked"
    # topology: optional aggregation hierarchy (federated/topology.py) —
    # None is the flat client->server star; TwoTierTopology(...) routes
    # uploads through location-clustered edge aggregators (per-tier times
    # on the virtual clock, edge_uplink/server_uplink ledger entries, and
    # cluster-aware cohort placement on the mesh executor).
    topology: Any = None
    # scheduler_backend: "auto" (vectorized fleet-scale core whenever the
    # policy supports it) | "vector" | "heapq" (per-arrival reference).
    # Both backends produce bitwise-identical traces.
    scheduler_backend: str = "auto"
    # fault_plan: optional seeded chaos schedule (federated/faults.py).
    # None (default) injects nothing and leaves every path bitwise-
    # historical. A `FaultPlan` adds client crashes with scheduler-side
    # retry, wire corruption + poisoned gradients screened server-side
    # (quarantine + quorum), reorder jitter, edge outages, and server
    # kills — all drawn from the plan's own hash stream, never the
    # training or scheduler RNGs.
    fault_plan: Optional[FaultPlan] = None
    # slo_monitor: optional `repro.obs.HealthMonitor` graded against the
    # finished trace at the end of every run() — failing rules emit
    # structured ``slo_violation`` obs events next to the run's own spans.
    slo_monitor: Optional[Any] = None

    def __post_init__(self):
        pq = getattr(self.model, "pq", None)
        dl = make_compressor(self.downlink_compressor, pq=pq)
        if dl is not None and hasattr(self.model, "downlink_compressor"):
            self.model = dataclasses.replace(self.model,
                                             downlink_compressor=dl)
        self.downlink = dl if dl is not None else \
            getattr(self.model, "downlink_compressor", None)
        # the uplink codec is INSTALLED into the model (or must match what
        # the model already runs) so the trained path and the measured
        # traffic never diverge
        up = make_compressor(self.uplink_compressor, pq=pq)
        if up is None:
            up = PQCompressor(pq) if (self.quantize and pq is not None) \
                else NoneCompressor()
        elif isinstance(up, NoneCompressor):
            if self.quantize and pq is not None:
                raise ValueError(
                    "uplink_compressor='none' conflicts with the model's PQ "
                    "config; pass quantize=False or a model without pq")
        elif isinstance(up, PQCompressor):
            if not self.quantize:
                raise ValueError("uplink_compressor='pq' needs quantize=True")
            if up.cfg != pq:
                self.model = dataclasses.replace(self.model, pq=up.cfg)
        elif hasattr(self.model, "uplink_compressor"):
            if not self.quantize:
                raise ValueError(
                    f"uplink_compressor={up.spec!r} needs quantize=True")
            self.model = dataclasses.replace(self.model, uplink_compressor=up)
        else:
            raise ValueError(
                f"{type(self.model).__name__} has no uplink_compressor "
                f"field; only 'pq'/'none' uplinks are realizable for it")
        self.uplink = up
        if self.codebook_delta_bits is not None:
            if not 1 <= self.codebook_delta_bits <= 16:
                raise ValueError(f"codebook_delta_bits="
                                 f"{self.codebook_delta_bits} not in [1, 16]")
            if not isinstance(up, PQCompressor) \
                    and not isinstance(self.downlink, PQCompressor):
                raise ValueError(
                    "codebook_delta_bits needs a pq uplink or downlink")
            if not self.quantize:
                raise ValueError("codebook_delta_bits needs quantize=True")
        if self.warm_start and not isinstance(up, PQCompressor):
            raise ValueError("warm_start needs a pq uplink")
        if (self.warm_start or self.error_feedback) and not self.quantize:
            raise ValueError("warm_start/error_feedback need quantize=True")
        # the execution engine owns the jitted steps and the device mapping
        # (federated/executor.py); it is bound AFTER the codecs above were
        # installed so its steps see the final model
        self.executor = make_executor(self.executor)
        self.executor.bind(self)
        self._wants_cut_state = self.warm_start or self.error_feedback
        self._global_q: Optional[QuantizerState] = None   # cohort-global
        self._global_q_nparts = 0                         # cohort size of it
        self._client_q: Dict[int, Any] = {}               # keyed by client id
        self._seed_q: Optional[Any] = None                # latest absorbed
        #                               per-client codebook: warm-start seed
        #                               for first-time clients
        self._ef_memory: Dict[int, Any] = {}              # per-client rows
        self._act_struct = None                           # per-client acts
        self.last_codebook_meta: Dict[str, Any] = {}
        # (uplink, downlink) wire-kind tags behind the measured payload
        # bytes; set by measure_round_bytes and fed to the scheduler's
        # per-round byte ledger (RoundRecord.ledger)
        self.last_wire_kinds = ("dense", "dense")
        # canary uplink payload (set by measure_round_bytes): the real
        # wire bytes a client would ship, corrupted per-plan in
        # _screen_cohort so detection runs against the actual wire format
        self._canary_payload: Optional[bytes] = None
        # per-round screening counters, merged into the trace after run()
        self._fault_log: Dict[int, Dict[str, int]] = {}
        # per-round screening verdicts (who was quarantined / was the
        # round voided), replayed onto the flight recorder's frames after
        # run() so exemplar lifecycles carry final server-side states
        self._screen_log: Dict[int, Dict[str, Any]] = {}
        self._rng = np.random.default_rng(self.seed)
        if self.fleet is None:
            self.fleet = uniform_fleet(self.data.num_clients)
        validate_fleet(self.fleet, self.data.num_clients)
        if self.policy is None:
            self.policy = FullSync()
        if self.topology is not None:
            # cluster the fleet once up front so the executor's placement
            # and every scheduler run see the same client->edge map
            self.topology.ensure(self.data.num_clients)
            self.executor.set_topology(self.topology)
        self.last_trace: Optional[Trace] = None

    def init_state(self, key: jax.Array) -> TrainState:
        return TrainState.create(self.model.init(key), self.optimizer)

    # ---- batch assembly ----------------------------------------------------
    def _sample(self, cid: int, client_key: jax.Array):
        return self.data.sample_batch(int(cid), client_key, self.client_batch,
                                      **(self.batch_kwargs or {}))

    def client_batch_for(self, cid: int, round_key: jax.Array):
        return self._sample(cid, jax.random.fold_in(round_key, int(cid)))

    def _cohort_parts(self, cids: Sequence[int], round_key: jax.Array,
                      versions: Optional[Sequence[int]] = None):
        """One batch per client, in ``cids`` order, each sampled with the
        key `client_batch_for` would give it (with ``versions``, the key of
        that version's round); all keys come from one `_cohort_keys` call."""
        keys = _cohort_keys(round_key, np.asarray(cids, np.uint32),
                            None if versions is None
                            else np.asarray(versions, np.uint32))
        return [self._sample(c, k) for c, k in zip(cids, keys)]

    def stack_batches(self, parts: Sequence[Dict[str, jax.Array]]):
        return _concat_cohort(parts)

    def cohort_batch(self, key: jax.Array) -> Dict[str, jax.Array]:
        ids = sample_clients(self._rng, self.data.num_clients, self.cohort)
        return self.stack_batches(self._cohort_parts(ids, key))

    def round(self, state: TrainState, key: jax.Array):
        """One synchronous server update on a fresh cohort, through the
        configured executor (the stacked default concatenates the cohort
        into one fused batch — the bitwise-historical path).

        The step donates ``state``: rebind it to the returned state and do
        not read the one passed in (copy it first to keep it)."""
        with obs.span("trainer.round", cat="trainer"):
            ids = sample_clients(self._rng, self.data.num_clients,
                                 self.cohort)
            return self.executor.execute(state, self._cohort_parts(ids, key))

    # ---- cross-round cut-layer state ---------------------------------------
    def _client_act_struct(self, params, part):
        """Shape/dtype of one client's cut activation (eval_shape, cached)."""
        if self._act_struct is None:
            acts = jax.eval_shape(
                lambda p, b: self.model.client_forward(p, b),
                params["client"], part)
            if isinstance(acts, tuple):   # TransformerLM: (acts, caches, aux)
                acts = acts[0]
            self._act_struct = acts
        return self._act_struct

    def _client_ef(self, cid: int):
        mem = self._ef_memory.get(int(cid))
        return mem if mem is not None \
            else jnp.zeros(self._act_struct.shape, self._act_struct.dtype)

    def _gather_client_q(self, cids):
        """Per-client codebook states stacked in participant order.

        Warm-start lineage is keyed by CLIENT ID on every path, so straggler
        policies that reshuffle cohort composition (DropSlowestK / Deadline
        survivors, AsyncBuffer flushes) keep each client's lineage intact.
        A client with no state yet is SEEDED from the most recently absorbed
        codebook (`_seed_q`) — activation distributions drift slowly, so a
        neighbor's codebook is a good warm initializer and the round stays
        warm instead of cold-flushing the whole cohort. Returns ``None``
        (cold round) only before any per-client state exists."""
        if not self._client_q and self._seed_q is None:
            return None
        states = [self._client_q.get(c, self._seed_q) for c in cids]
        if any(s is None for s in states):   # no seed to warm first-timers
            return None
        return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *states)

    def _cut_state_for(self, participants, params, parts, stacked: bool):
        """Assemble the round's `CutState` (or None when both features are
        off). Stacked path: per-client codebooks stacked in participant
        order when the model quantizes per client (falling back to the
        cohort-global codebook for models with one codebook per cohort) +
        per-client EF rows concatenated in participant order. Per-client
        path (AsyncBuffer flushes, every mesh-executor update): every leaf
        gains a leading client axis."""
        if not self._wants_cut_state:
            return None
        self._client_act_struct(params, parts[0])
        cids = [int(a.client) for a in participants]
        if stacked:
            q = None
            if self.warm_start:
                q = self._gather_client_q(cids)
                if q is None:
                    # cohort-global lineage (one codebook per cohort,
                    # model.client_batch == 0) — or a manually injected
                    # stacked state, which only fits the cohort size that
                    # produced it: fall back to cold on a count change
                    q = self._global_q
                    if q is not None and q.codebooks.ndim > 3 \
                            and len(cids) != self._global_q_nparts:
                        q = None
            ef = jnp.concatenate([self._client_ef(c) for c in cids], axis=0) \
                if self.error_feedback else None
            return CutState(quantizer=q, ef_memory=ef)
        q = self._gather_client_q(cids) if self.warm_start else None
        ef = jnp.stack([self._client_ef(c) for c in cids], axis=0) \
            if self.error_feedback else None
        return CutState(quantizer=q, ef_memory=ef)

    def _absorb_cut_state(self, participants, new_cut, stacked: bool):
        """Scatter a step's returned `CutState` back into the per-client
        slots keyed by client id (per-client-axis state may carry padded
        executor slots past ``len(participants)``; they are ignored). State
        with one codebook per cohort — or a stacked axis that does not
        match the participant count — lands in the cohort-global slot."""
        if new_cut is None:
            return
        cids = [int(a.client) for a in participants]
        if self.warm_start and new_cut.quantizer is not None:
            q = new_cut.quantizer
            per_client = q.codebooks.ndim > 3 \
                and q.codebooks.shape[0] >= len(cids) \
                and (not stacked or q.codebooks.shape[0] == len(cids))
            if per_client:
                for i, c in enumerate(cids):
                    self._client_q[c] = jax.tree.map(lambda x: x[i], q)
                self._seed_q = self._client_q[cids[-1]]
            else:
                self._global_q = q
                self._global_q_nparts = len(cids)
        if self.error_feedback and new_cut.ef_memory is not None:
            if stacked:
                rows = self._act_struct.shape[0]
                for i, c in enumerate(cids):
                    self._ef_memory[c] = \
                        new_cut.ef_memory[i * rows:(i + 1) * rows]
            else:
                for i, c in enumerate(cids):
                    self._ef_memory[c] = new_cut.ef_memory[i]

    # ---- server-side admission screening (chaos plans only) ----------------
    def _screen_cohort(self, inj, update_idx: int, participants, parts,
                       weights):
        """Inject the plan's payload faults, then quarantine every
        contribution that fails the server's admission checks before any
        of it can touch the aggregate.

        Corruption is applied to the round's canary — the real uplink
        wire frame — and detection is the actual `federated/wire.py`
        decode (CRC + typed errors), so a corrupt contribution is either
        caught in transit (quarantined) or counted in
        ``corrupt_undetected`` (the chaos canary: must stay 0). Poisoned
        clients ship NaN-filled tensors; the finiteness screen catches
        them regardless of how they were poisoned. Survivors keep their
        own staleness weights — aggregation renormalizes over the kept
        cohort exactly as under straggler cuts. A round whose survivor
        fraction falls below ``quorum_fraction`` is VOIDED: no server
        update, counters only.

        Returns ``(participants, parts, weights, fault_counters)`` —
        empty lists mean the round was voided.
        """
        cids = np.asarray([int(a.client) for a in participants], np.int64)
        poison = inj.poison_mask(update_idx, cids)
        corrupt = inj.corrupt_mask(update_idx, cids)
        fl: Dict[str, int] = {}
        if not poison.any() and not corrupt.any():
            return participants, parts, weights, fl
        parts = list(parts)
        for i in np.nonzero(poison)[0]:
            parts[i] = jax.tree.map(
                lambda x: jnp.full_like(x, jnp.nan)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, parts[i])
        keep = np.ones(len(parts), bool)
        undetected = 0
        canary = self._canary_payload
        for i in range(len(parts)):
            if corrupt[i] and canary is not None:
                bad = inj.corrupt_payload(canary, update_idx, int(cids[i]))
                try:
                    wire.decode_payload(bad)
                except wire.WireError:
                    keep[i] = False       # caught in transit -> quarantined
                    continue
                undetected += 1           # CRC missed: canary assertion trips
            if not keep[i]:
                continue
            for leaf in jax.tree.leaves(parts[i]):
                if jnp.issubdtype(leaf.dtype, jnp.floating) \
                        and not bool(jnp.isfinite(leaf).all()):
                    keep[i] = False       # non-finite -> quarantined
                    break
        quarantined = int((~keep).sum())
        if quarantined:
            fl["quarantined"] = quarantined
        if undetected:
            fl["corrupt_undetected"] = undetected
        voided = \
            int(keep.sum()) < self.fault_plan.quorum_fraction * len(parts)
        if quarantined or voided:
            self._screen_log[update_idx] = {
                "quarantined": [int(c) for c in cids[~keep]],
                "voided": voided}
        if voided:
            fl["round_voided"] = 1
            obs.event("fault.round_voided", cat="faults", round=update_idx,
                      quarantined=quarantined, cohort=len(parts))
            return [], [], [], fl
        if quarantined:
            participants = [a for a, k in zip(participants, keep) if k]
            parts = [p for p, k in zip(parts, keep) if k]
            if weights is not None:
                weights = [w for w, k in zip(weights, keep) if k]
        return participants, parts, weights, fl

    # ---- wire measurement --------------------------------------------------
    def measure_round_bytes(self, state: TrainState, key: jax.Array):
        """Measured per-client (uplink, downlink) payload bytes for a round.

        One real client forward feeds both directions. Uplink: the cut
        activations through the configured uplink codec and the tagged wire
        format (`federated/wire.py`). Downlink: the cut-layer gradient
        message through the downlink codec — its payload layout is
        shape-determined (indices count, code widths), so the activation
        tensor stands in for the gradient and a single measurement is exact
        for every round. ``none`` on either side measures the dense tensor
        at its native dtype.

        With ``codebook_delta_bits`` set, each pq direction is measured as
        the steady-state ``pq-delta`` payload: a second round's tensor is
        quantized warm-started from the first, its codebook is delta-encoded
        against the acked (fp16-decoded) round-0 reference, and the measured
        codebook-bytes reduction lands in ``self.last_codebook_meta`` (and
        the run's ``trace.meta``) — uplink keys unprefixed (the historical
        layout), downlink keys under ``downlink_``.
        """
        batch = self.data.sample_batch(0, key, self.client_batch,
                                       **(self.batch_kwargs or {}))
        acts = self.executor.client_forward(state.params["client"], batch)
        if isinstance(acts, tuple):   # TransformerLM returns (acts, aux...)
            acts = acts[0]
        acts2 = acts.reshape(-1, acts.shape[-1])
        raw_bytes = int(acts.size * jnp.dtype(acts.dtype).itemsize)

        def measured(compressor: Optional[CutCompressor]):
            # quantize=False disables the cut codecs in the training VJP
            # (models gate on it), so the measurement must stay dense too
            if not self.quantize or compressor is None \
                    or compressor.name == "none":
                return raw_bytes, "dense", None
            comp = compressor.compress(acts2)
            payload = compressor.wire_payload(
                comp, value_dtype=self.codebook_wire_dtype)
            # the kind tag the receiver will dispatch on — read from the
            # actual payload header so chains report their outermost stage
            return len(payload), wire.payload_kind(payload), payload

        with obs.span("trainer.measure_round_bytes", cat="wire"):
            uplink_bytes, up_kind, up_payload = measured(self.uplink)
            downlink_bytes, down_kind, _ = measured(self.downlink)
            # the chaos canary: one real uplink frame (dense tensors get a
            # dense frame; pq-delta measurement keeps the self-contained pq
            # frame — delta decode needs receiver state a canary lacks)
            self._canary_payload = up_payload if up_payload is not None \
                else wire.encode_dense(np.asarray(acts2, np.float32),
                                       acts2.shape[0], acts2.shape[1],
                                       "float32")
            self.last_codebook_meta = {}
            if self.codebook_delta_bits is not None and self.quantize:
                acts_b = self._second_round_acts(state, key)
                if isinstance(self.uplink, PQCompressor):
                    uplink_bytes = self._measure_delta_direction(
                        self.uplink.cfg, acts2, acts_b, uplink_bytes,
                        prefix="", bytes_key="uplink_bytes")
                    up_kind = "pq-delta"
                if isinstance(self.downlink, PQCompressor):
                    # same machinery, other direction: the gradient
                    # message's codebooks delta-encoded against the
                    # previous round's acked reference (the activation
                    # tensor stands in for the gradient, as for the
                    # non-delta downlink measurement)
                    downlink_bytes = self._measure_delta_direction(
                        self.downlink.cfg, acts2, acts_b, downlink_bytes,
                        prefix="downlink_", bytes_key="downlink_bytes")
                    down_kind = "pq-delta"
        self.last_wire_kinds = (up_kind, down_kind)
        return uplink_bytes, downlink_bytes

    def _second_round_acts(self, state: TrainState, key: jax.Array):
        """A second round's cut tensor (for steady-state delta payloads)."""
        batch2 = self.data.sample_batch(0, jax.random.fold_in(key, 1),
                                        self.client_batch,
                                        **(self.batch_kwargs or {}))
        acts_b = self.executor.client_forward(state.params["client"], batch2)
        if isinstance(acts_b, tuple):
            acts_b = acts_b[0]
        return acts_b.reshape(-1, acts_b.shape[-1])

    def _measure_delta_direction(self, cfg, acts2, acts_b, full_bytes: int,
                                 *, prefix: str, bytes_key: str) -> int:
        """Steady-state `pq-delta` payload bytes for one direction.

        Round 0 quantizes cold and ships full codebooks; the acked
        reference is what the receiver decoded — the codebook at wire
        fidelity, not the sender's private fp32 copy. Round 1 quantizes
        warm-started from round 0's `QuantizerState` and ships b-bit
        codebook deltas against the reference."""
        qb1, qstate = quantize_stateful(acts2, cfg)
        # loopback of bytes we just encoded — nothing untrusted on this wire
        ref = wire.decode_bytes(  # fedlint: disable=unchecked-wire-decode
            wire.encode_bytes(qb1, self.codebook_wire_dtype)) \
            .codebooks.astype(np.float32)
        qb2, _ = quantize_stateful(acts_b, cfg, qstate)
        payload, _ = wire.encode_pq_delta(qb2, ref, self.codebook_delta_bits)
        d = int(acts2.shape[-1])
        cb_full = int(np.prod(cfg.codebook_shape(d))) \
            * wire._np_dtype(self.codebook_wire_dtype).itemsize
        code_bytes = len(wire.encode_bytes(qb2, self.codebook_wire_dtype)) \
            - wire.HEADER_BYTES - wire.CRC_BYTES - cb_full
        cb_delta = len(payload) - wire.HEADER_BYTES - wire.CRC_BYTES \
            - code_bytes
        self.last_codebook_meta.update({
            f"{prefix}codebook_delta_bits": self.codebook_delta_bits,
            f"{bytes_key}_full_codebook": full_bytes,
            f"{bytes_key}_delta_codebook": len(payload),
            f"{prefix}codebook_bytes_full": cb_full,
            f"{prefix}codebook_bytes_delta": cb_delta,
            f"{prefix}codebook_bytes_reduction": cb_full / max(cb_delta, 1),
        })
        return len(payload)

    def measure_uplink_bytes(self, state: TrainState, key: jax.Array) -> int:
        return self.measure_round_bytes(state, key)[0]

    def measure_downlink_bytes(self, state: TrainState, key: jax.Array) -> int:
        return self.measure_round_bytes(state, key)[1]

    def measure_dense_bytes(self, state: TrainState, key: jax.Array) -> int:
        """The uncompressed cut tensor (either direction's dense baseline)."""
        batch = self.data.sample_batch(0, key, self.client_batch,
                                       **(self.batch_kwargs or {}))
        acts = self.executor.client_forward(state.params["client"], batch)
        if isinstance(acts, tuple):
            acts = acts[0]
        return int(acts.size * jnp.dtype(acts.dtype).itemsize)

    # ---- scheduled run -----------------------------------------------------
    def run(self, steps: int, key: jax.Array, log_every: int = 0,
            state: Optional[TrainState] = None,
            cursor: Optional[Dict[str, Any]] = None,
            on_round=None):
        """Run ``steps`` server updates through the scheduler.

        Returns (final state, history) where history holds one dict per
        server update: the step metrics (host-synced once, at the end of the
        run — not per round) plus the round's simulation fields. The full
        `Trace` is kept in ``self.last_trace``.

        ``state`` (optional) continues training from an existing
        `TrainState` instead of a fresh init — what the trace-driven
        autoscaler uses to re-run segments of one training run under
        successive (cohort, policy, compressor) plans
        (``federated/autoscale.py``). The caller's state is copied on
        entry: the executors' steps donate their input buffers,
        and donation must never reach arrays the caller still owns.

        ``cursor`` / ``on_round`` are the crash-recovery hooks forwarded
        to `Scheduler.run` (sync policies only): a cursor resumes the
        virtual clock + scheduler RNG mid-run with ``steps`` as the
        absolute end index, and ``on_round(rd, cursor)`` fires after
        each completed round — `federated/recovery.py` snapshots there.
        """
        state = self.init_state(key) if state is None \
            else jax.tree.map(jnp.copy, state)
        # per-round step metrics stay on device; MetricsBuffer.flush is the
        # run's single blocking transfer (tests/test_obs.py counts it)
        metrics_buf = obs.MetricsBuffer()
        inj = make_injector(self.fault_plan)
        self._fault_log = {}
        self._screen_log = {}

        def execute(update_idx: int, participants: Sequence[Arrival],
                    weights: Sequence[float]) -> Dict:
            nonlocal state
            parts = self._cohort_parts([a.client for a in participants], key,
                                       [a.version for a in participants])
            if inj is not None and parts:
                participants, parts, weights, fl = self._screen_cohort(
                    inj, update_idx, participants, parts, weights)
                if fl:
                    self._fault_log[update_idx] = fl
                if not parts:
                    return {}   # round voided: below quorum, no update
            # AsyncBuffer flushes run the per-contribution staleness
            # weighting (FedBuff): each client's gradient split is
            # discounted by ITS OWN staleness before aggregation — not by
            # the cohort mean. Every async flush takes this path (even
            # all-fresh buffers) so the per-client quantization granularity
            # is consistent across a run instead of flipping with the
            # staleness draw. Synchronous policies pass weights=None and
            # the executor picks its fused/cohort semantics.
            is_async = isinstance(self.policy, AsyncBuffer)
            per_client = self.executor.per_client_layout(is_async)
            cut_in = self._cut_state_for(participants, state.params, parts,
                                         stacked=not per_client)
            state, metrics = self.executor.execute(
                state, parts, weights if is_async else None, cut_in)
            self._absorb_cut_state(participants,
                                   metrics.pop("cut_state", None),
                                   stacked=not per_client)
            metrics_buf.record(metrics)
            if log_every and update_idx % log_every == 0:
                # the only mid-run host sync, at the caller-chosen cadence
                logger.info("step %d: loss=%.4f", update_idx,
                            float(metrics.get("loss", 0.0)))  # fedlint: disable=host-sync-in-callback
            return metrics

        scheduler = Scheduler(fleet=self.fleet, policy=self.policy,
                              client_step_seconds=self.client_step_seconds,
                              server_step_seconds=self.server_step_seconds,
                              seed=self.seed,
                              backend=self.scheduler_backend,
                              topology=self.topology,
                              faults=self.fault_plan)
        uplink, downlink = self.measure_round_bytes(
            state, jax.random.fold_in(key, 0))
        trace = scheduler.run(
            steps, sample_cohort=lambda rd: sample_clients(
                self._rng, self.data.num_clients, self.cohort),
            uplink_bytes=uplink, downlink_bytes=downlink, execute=execute,
            placement=self.executor.place,
            wire_kinds=self.last_wire_kinds,
            cursor=cursor, on_round=on_round)
        dl = self.downlink
        trace.meta.update({
            "uplink_compressor": getattr(self.uplink, "spec",
                                         self.uplink.name),
            "downlink_compressor": "none" if dl is None
            else getattr(dl, "spec", dl.name),
            "uplink_bytes_per_client": uplink,
            "downlink_bytes_per_client": downlink,
            "warm_start": self.warm_start,
            "error_feedback": self.error_feedback,
            "stochastic_downlink": self.stochastic_downlink,
            "executor": self.executor.name,
            "executor_shards": getattr(self.executor, "num_shards", 1),
            "uplink_wire_kind": self.last_wire_kinds[0],
            "downlink_wire_kind": self.last_wire_kinds[1],
            "scheduler_backend": scheduler._resolve_backend(),
        })
        if self.topology is not None:
            trace.meta.update(self.topology.meta())
        trace.meta.update(self.last_codebook_meta)

        # one blocking transfer for the whole run
        host_metrics = metrics_buf.flush()
        history: List[Dict[str, float]] = []
        it = iter(host_metrics)
        for rec in trace:
            # merge server-side screening counters into the scheduler's
            # wire-level fault counters for the same round
            fl = self._fault_log.get(rec.round)
            if fl:
                rec.faults.update(fl)
            floats = next(it) if rec.metrics else {}
            rec.metrics = floats
            entry = dict(floats, step=rec.round, t_start=rec.t_start,
                         t_end=rec.t_end, uplink_bytes=rec.uplink_bytes,
                         downlink_bytes=rec.downlink_bytes,
                         participants=len(rec.participants),
                         dropped=len(rec.dropped))
            history.append(entry)
        # replay server-side screening verdicts onto the flight frames the
        # scheduler recorded at wire level (aggregated -> quarantined /
        # voided), so the emitted lifecycles show final outcomes
        if trace.flights and self._screen_log:
            flightlib.apply_screening(trace.flights, self._screen_log)
        self.last_trace = trace
        obs.log_trace(trace)   # no-op unless a recorder is configured
        if self.slo_monitor is not None:
            self.slo_monitor.check(trace)
        return state, history
