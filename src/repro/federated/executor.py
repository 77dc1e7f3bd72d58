"""Cohort execution engine: who runs the round's client math, and where.

The virtual-clock `Scheduler` decides WHO participates in a server update;
the train steps in ``core/fedlite.py`` define WHAT one update computes.
This module owns the layer between them — HOW a cohort's per-client
forward/backward work is mapped onto devices. `FederatedTrainer` routes
``round`` / ``run``'s execute hook / ``measure_round_bytes`` through a
`CohortExecutor`, selected by spec string (``executor="stacked"`` /
``"mesh"`` / ``"mesh(shards=4)"``) or instance:

  * ``stacked`` — the historical single-device path, extracted verbatim:
    synchronous policies concatenate the cohort's client batches into one
    fused batch for ``make_train_step``; `AsyncBuffer` flushes go through
    ``make_weighted_step``'s per-contribution staleness weighting. The
    default — bitwise-identical to the pre-executor trainer (asserted in
    tests/test_executor.py).
  * ``mesh``    — cohort-parallel execution over the ``clients`` axis of a
    1-D device mesh (``launch/mesh.make_clients_mesh``, host-count-aware:
    a CPU CI runner forces 2-4 host devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``). Client-major
    arrays — batches, per-client PRNG keys, error-feedback memories,
    `CutState`s — are placed with ``NamedSharding(mesh, P("clients"))``;
    each shard computes its local clients' gradients and the weighted
    combine crosses shards once, as an explicit psum
    (``core/fedlite.make_mesh_step``). Cohorts that do not divide the
    shard count are padded with zero-masked duplicate slots.

Every scheduler policy (FullSync / DropSlowestK / Deadline / AsyncBuffer)
executes unchanged on either backend: policies see cohorts and arrival
times, never devices. The executor also assigns each surviving participant
its shard (``place``) — the scheduler threads the placement into the
round's `Arrival`s so traces record where every client ran.

Semantics: the mesh backend reproduces the stacked backend's round metrics
and gradients (allclose; float reassociation only) whenever the model
quantizes per client (``model.client_batch == trainer.client_batch``) or
runs unquantized. A cohort-GLOBAL codebook (``model.client_batch == 0``
with PQ on) is not shard-local — the mesh executor then clusters per
client instead, which is the federated-realistic granularity; a warning is
logged for the divergence. The λ-correction scale difference between the
fused synchronous step and per-client gradients is reconciled by
``make_mesh_step``'s ``correction_scope`` (see its docstring).

New backends register through ``register_executor`` — e.g. a multi-host
pod backend mapping cohorts onto ``("pod", "clients")`` — without touching
the trainer.
"""

from __future__ import annotations

import dataclasses
import logging
import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.fedlite import (TrainState, make_mesh_step, make_train_step,
                                make_weighted_step)
from repro.sharding.ctx import (CLIENTS_AXIS, clients_sharding,
                                replicated_sharding)

logger = logging.getLogger(__name__)


class CohortExecutor:
    """Base class: maps one server update's cohort onto devices.

    Lifecycle: `FederatedTrainer.__post_init__` resolves the spec via
    ``make_executor`` and calls ``bind(trainer)`` exactly once — after the
    trainer has installed the cut-layer codecs into the model — so the
    executor builds its jitted steps against the final model. All entry
    points take/return the trainer's `TrainState`; metrics may stay on
    device (the trainer host-syncs once per run).
    """
    name: str = "base"

    def bind(self, trainer) -> None:
        raise NotImplementedError

    def _claim(self, trainer) -> None:
        """Attach to ``trainer``, refusing silent re-targeting: one executor
        instance holds one trainer's jitted steps, and sharing it across
        trainers would cross-wire the first trainer to the second's
        model/optimizer."""
        bound = getattr(self, "trainer", None)
        if bound is not None and bound is not trainer:
            raise ValueError(
                f"{type(self).__name__} is already bound to another trainer;"
                " construct one executor per FederatedTrainer")
        self.trainer = trainer

    # ---- cohort layout -----------------------------------------------------
    def per_client_layout(self, is_async: bool) -> bool:
        """Whether cut-layer state must be client-major for this path
        (vs the stacked synchronous layout: concatenated EF rows +
        cohort-level codebook state)."""
        raise NotImplementedError

    def place(self, participants: Sequence[Any]) -> List[Any]:
        """Annotate each `Arrival` with the shard that will execute it."""
        with obs.span("executor.place", cat="executor", backend=self.name,
                      clients=len(participants)):
            return [dataclasses.replace(a, shard=0) for a in participants]

    # ---- topology awareness ------------------------------------------------
    def set_topology(self, topology: Any) -> None:
        """Make placement cluster-aware under hierarchical aggregation.

        The trainer calls this (after ``topology.ensure``) so ``place``
        can co-locate clients of the same edge cluster on the same shard
        — the shard-local partial sums then mirror the edges' partial
        sums, keeping the pre-combination communication pattern aligned
        between the simulation's tiers and the device mesh. The stacked
        single-device path stores but ignores it.
        """
        self._cluster_of = None if topology is None \
            else getattr(topology, "cluster_of", None)

    # ---- execution ---------------------------------------------------------
    def execute(self, state: TrainState, parts: Sequence[Dict],
                weights: Optional[Sequence[float]] = None,
                cut_state: Any = None) -> Tuple[TrainState, Dict]:
        """Run one server update over ``parts`` (one batch per client, in
        participant order). ``weights=None`` selects synchronous semantics;
        a weight vector selects the per-contribution (FedBuff) semantics
        with ``cut_state`` in client-major layout."""
        raise NotImplementedError

    def lower(self, state: TrainState, parts: Sequence[Dict]):
        """The synchronous step ``execute(state, parts)`` would run, lowered
        for its arguments (``.compile()`` it to inspect the program)."""
        raise NotImplementedError

    # ---- measurement routing ----------------------------------------------
    def client_forward(self, client_params, batch):
        """One client's cut activations for the wire measurement."""
        return self.trainer.model.client_forward(client_params, batch)


def _stack_parts(parts: Sequence[Dict]) -> Dict:
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *parts)


@dataclasses.dataclass
class StackedExecutor(CohortExecutor):
    """The historical single-device path (bitwise-preserving default)."""
    name: str = dataclasses.field(default="stacked", init=False)

    def bind(self, trainer) -> None:
        self._claim(trainer)
        step_key = jax.random.PRNGKey(trainer.seed) \
            if trainer.stochastic_downlink else None
        # both steps donate the input state (round() and run() rebind it):
        # without donation the old and new params + optimizer state are live
        # at once, which a published-width model does not fit on one chip
        self._step = make_train_step(trainer.model, trainer.optimizer,
                                     quantize=trainer.quantize,
                                     step_key=step_key)
        self._weighted_step = make_weighted_step(
            trainer.model, trainer.optimizer, quantize=trainer.quantize,
            donate=True, step_key=step_key)

    def per_client_layout(self, is_async: bool) -> bool:
        return is_async

    def lower(self, state, parts):
        return self._step.lower(state, self.trainer.stack_batches(parts))

    def execute(self, state, parts, weights=None, cut_state=None):
        # the span measures host dispatch time (the step is async on
        # device); blocking for device completion here would add the very
        # host sync the metrics buffer exists to avoid
        with obs.span("executor.execute", cat="executor", backend=self.name,
                      clients=len(parts),
                      mode="sync" if weights is None else "weighted"):
            if weights is None:
                # one definition of the bitwise-critical batch fusing
                step = self._step
                args = (state, self.trainer.stack_batches(parts))
            else:
                step = self._weighted_step
                args = (state, _stack_parts(parts),
                        jnp.asarray(weights, jnp.float32))
            if cut_state is not None:
                args += (cut_state,)
            # only the call of the jitted step: everything before it is
            # round assembly
            with obs.span("executor.dispatch", cat="executor"):
                return step(*args)


@dataclasses.dataclass
class MeshExecutor(CohortExecutor):
    """Cohort-parallel execution over the ``clients`` mesh axis.

    ``shards=0`` builds a host-count-aware mesh over every visible device;
    pass ``shards=n`` or an explicit ``mesh`` (any mesh with a ``clients``
    axis) to pin the width. Jitted steps are built lazily per semantics
    (synchronous vs weighted) on first use; one compile per distinct padded
    cohort size, like the stacked path's one-per-survivor-count.
    """
    shards: int = 0
    mesh: Any = None
    name: str = dataclasses.field(default="mesh", init=False)

    def bind(self, trainer) -> None:
        from repro.launch.mesh import make_clients_mesh
        self._claim(trainer)
        if self.mesh is None:
            self.mesh = make_clients_mesh(self.shards)
        if CLIENTS_AXIS not in self.mesh.axis_names:
            raise ValueError(f"mesh {self.mesh.axis_names} has no "
                             f"{CLIENTS_AXIS!r} axis")
        self.num_shards = int(self.mesh.shape[CLIENTS_AXIS])
        self._steps: Dict[str, Callable] = {}
        self._step_key = jax.random.PRNGKey(trainer.seed) \
            if trainer.stochastic_downlink else None
        if trainer.quantize and getattr(trainer.model, "pq", None) is not None \
                and getattr(trainer.model, "client_batch", 0) == 0:
            logger.warning(
                "mesh executor with a cohort-global PQ codebook "
                "(model.client_batch=0): clustering runs per client on the "
                "mesh — set model.client_batch=trainer.client_batch for "
                "stacked-parity quantization granularity")

    def per_client_layout(self, is_async: bool) -> bool:
        return True

    # ---- placement ---------------------------------------------------------
    def _slot_count(self, n: int) -> int:
        """Padded client-slot count: the smallest multiple of the shard
        width that fits the cohort."""
        return max(-(-n // self.num_shards) * self.num_shards,
                   self.num_shards)

    def place(self, participants):
        """Contiguous-block shard assignment; cluster-major when a
        topology is installed.

        With ``set_topology``, participants are stably sorted by edge
        cluster before the block split, so one shard's slice holds whole
        clusters wherever sizes allow — the scheduler records ``place``'s
        output order, so the trace, the executed cohort and the staleness
        weights all follow the reordering consistently.
        """
        with obs.span("executor.place", cat="executor", backend=self.name,
                      clients=len(participants)):
            parts = list(participants)
            cluster_of = getattr(self, "_cluster_of", None)
            if cluster_of is not None and parts:
                order = np.argsort(
                    np.asarray([int(cluster_of[a.client]) for a in parts]),
                    kind="stable")
                parts = [parts[i] for i in order]
            local = self._slot_count(len(parts)) // self.num_shards
            return [dataclasses.replace(a, shard=i // local)
                    for i, a in enumerate(parts)]

    # ---- execution ---------------------------------------------------------
    def _get_step(self, scope: str) -> Callable:
        if scope not in self._steps:
            self._steps[scope] = make_mesh_step(
                self.trainer.model, self.trainer.optimizer, self.mesh,
                quantize=self.trainer.quantize,
                step_key=self._step_key, correction_scope=scope)
        return self._steps[scope]

    def _pad(self, tree, pad: int):
        """Grow every leaf's client axis by ``pad`` duplicate (masked)
        slots — duplicating the last real client keeps the padded compute
        numerically tame (no all-zero batches through PQ seeding)."""
        if pad == 0:
            return tree
        return jax.tree.map(
            lambda x: jnp.concatenate(
                [x, jnp.repeat(x[-1:], pad, axis=0)], axis=0), tree)

    def _placed_args(self, state, parts, weights, cut_state):
        """The mesh step's arguments, padded to the slot count and placed:
        client-major arrays sharded over ``clients``, the state
        replicated."""
        n = len(parts)
        pad = self._slot_count(n) - n
        w = jnp.asarray(list(weights) if weights is not None else [1.0] * n,
                        jnp.float32)
        w = jnp.concatenate([w, jnp.ones((pad,), jnp.float32)]) \
            if pad else w
        mask = jnp.concatenate([jnp.ones((n,), jnp.float32),
                                jnp.zeros((pad,), jnp.float32)]) \
            if pad else jnp.ones((n,), jnp.float32)
        sh_clients = clients_sharding(self.mesh)
        batches = jax.device_put(self._pad(_stack_parts(parts), pad),
                                 sh_clients)
        w = jax.device_put(w, sh_clients)
        mask = jax.device_put(mask, sh_clients)
        if cut_state is not None:
            cut_state = jax.device_put(self._pad(cut_state, pad), sh_clients)
        state = jax.device_put(state, replicated_sharding(self.mesh))
        return state, batches, w, mask, cut_state

    def lower(self, state, parts):
        return self._get_step("cohort").lower(
            *self._placed_args(state, parts, None, None))

    def execute(self, state, parts, weights=None, cut_state=None):
        sync = weights is None
        n = len(parts)
        slots = self._slot_count(n)
        with obs.span("executor.execute", cat="executor", backend=self.name,
                      clients=n, slots=slots, shards=self.num_shards,
                      mode="sync" if sync else "weighted"):
            step = self._get_step("cohort" if sync else "client")
            args = self._placed_args(state, parts, weights, cut_state)
            with obs.span("executor.dispatch", cat="executor"):
                state, metrics = step(*args)
            if sync:
                # keep synchronous metrics key-compatible with the stacked
                # path
                metrics.pop("mean_staleness_weight", None)
            return state, metrics


# ---------------------------------------------------------------------------
# registry + spec parsing
# ---------------------------------------------------------------------------

_EXECUTORS: Dict[str, Callable[..., CohortExecutor]] = {}


def register_executor(name: str,
                      factory: Callable[..., CohortExecutor]) -> None:
    """Register (or replace) a named executor factory."""
    _EXECUTORS[name] = factory


register_executor("stacked", lambda **kw: StackedExecutor(**kw))
register_executor("mesh", lambda **kw: MeshExecutor(**kw))


def available_executors() -> Tuple[str, ...]:
    return tuple(sorted(_EXECUTORS))


_SPEC_RE = re.compile(r"^(?P<name>[a-zA-Z_]\w*)(?:\((?P<args>.*)\))?$")


def make_executor(spec) -> CohortExecutor:
    """Build an executor from a spec string (``"stacked"``, ``"mesh"``,
    ``"mesh(shards=4)"``) or pass an instance through unchanged. ``None``
    resolves to the stacked default."""
    if spec is None:
        return StackedExecutor()
    if isinstance(spec, CohortExecutor):
        return spec
    m = _SPEC_RE.match(spec.strip())
    if not m or m.group("name") not in _EXECUTORS:
        raise ValueError(f"unknown executor spec {spec!r}; registered: "
                         f"{available_executors()}")
    kwargs: Dict[str, Any] = {}
    for part in (m.group("args") or "").split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(f"executor arg {part!r} must be key=value")
        k, v = part.split("=", 1)
        kwargs[k.strip()] = int(v.strip()) if v.strip().isdigit() \
            else v.strip()
    return _EXECUTORS[m.group("name")](**kwargs)
