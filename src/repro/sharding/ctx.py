"""Sharding context: a process-global mesh used by activation constraints.

Model code calls ``shard(x, "batch_axes", None, "model")`` at key points.
When no mesh is installed (unit tests on a single CPU device) the call is a
no-op, so the same model code runs unsharded on one device and fully sharded
under the production mesh without signature pollution.

Axis names that are not present in the installed mesh are silently dropped
from the spec, so ``shard(x, ("pod", "data"), None)`` works both on the
single-pod ``("data", "model")`` mesh and the multi-pod
``("pod", "data", "model")`` mesh.
"""

from __future__ import annotations

import contextlib
from typing import Optional, Sequence, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_MESH: Optional[Mesh] = None

AxisEntry = Union[None, str, Sequence[str]]

# The cohort-parallel mesh axis: one shard = one slice of a round's client
# cohort. Built by ``launch/mesh.make_clients_mesh`` and consumed by the
# ``"mesh"`` cohort executor (``federated/executor.py``), which places
# client-major arrays (batches, PRNG keys, EF memories, CutStates) with
# ``NamedSharding(mesh, P(CLIENTS_AXIS))`` and combines shard-local
# per-client gradients with an explicit psum over this axis.
CLIENTS_AXIS = "clients"


def clients_sharding(mesh: Mesh) -> NamedSharding:
    """`NamedSharding` placing a client-major array's leading axis over the
    ``clients`` mesh axis (remaining dims replicated)."""
    return NamedSharding(mesh, P(CLIENTS_AXIS))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully-replicated placement on ``mesh`` (the train state's layout
    under the cohort-parallel executor)."""
    return NamedSharding(mesh, P())


def set_mesh(mesh: Optional[Mesh]) -> None:
    """Install (or clear, with None) the process-global mesh."""
    global _MESH
    _MESH = mesh


def current_mesh() -> Optional[Mesh]:
    return _MESH


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    """Context manager: install ``mesh`` for the duration of the block."""
    global _MESH
    prev = _MESH
    _MESH = mesh
    try:
        if mesh is not None:
            with mesh:
                yield mesh
        else:
            yield None
    finally:
        _MESH = prev


def axis_size(name: str) -> int:
    """Size of a mesh axis, or 1 if no mesh / axis absent."""
    if _MESH is None or name not in _MESH.axis_names:
        return 1
    return _MESH.shape[name]


def _filter_entry(entry: AxisEntry, names) -> AxisEntry:
    """Drop axis names that the installed mesh does not have."""
    if entry is None:
        return None
    if isinstance(entry, str):
        return entry if entry in names else None
    kept = tuple(a for a in entry if a in names)
    if not kept:
        return None
    if len(kept) == 1:
        return kept[0]
    return kept


def filter_spec(spec: P, mesh: Optional[Mesh] = None) -> P:
    """Rewrite a PartitionSpec so it only references axes of ``mesh``."""
    mesh = mesh if mesh is not None else _MESH
    if mesh is None:
        return P()
    names = set(mesh.axis_names)
    return P(*[_filter_entry(e, names) for e in spec])


def _axis_prod(entry: AxisEntry) -> int:
    if entry is None or _MESH is None:
        return 1
    names = (entry,) if isinstance(entry, str) else entry
    n = 1
    for a in names:
        n *= _MESH.shape[a]
    return n


def shard(x: jax.Array, *entries: AxisEntry) -> jax.Array:
    """Apply a sharding constraint if a mesh is installed; no-op otherwise.

    Each spec entry is additionally guarded by divisibility: a dim that does
    not divide its axis product is replicated instead (so the same constraint
    serves train (S=4096), decode (S=1) and smoke shapes)."""
    if _MESH is None:
        return x
    spec = filter_spec(P(*entries), _MESH)
    guarded = [e if d % _axis_prod(e) == 0 else None
               for d, e in zip(x.shape, list(spec) + [None] * x.ndim)]
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_MESH, P(*guarded)))


def shard_residual(x: jax.Array) -> jax.Array:
    """Residual-stream layout (B, S, D): batch over ("pod","data") AND
    sequence over "model" — Megatron-style sequence parallelism. Between
    blocks only norms/adds happen, so seq-sharding there divides the
    layer-scan's saved backward carries by the model-axis size; XLA inserts
    the all-gather (into attention/MLP) and reduce-scatter (out of the
    row-parallel projections) automatically."""
    return shard(x, ("pod", "data"), "model", None)


def named_sharding(*entries: AxisEntry) -> Optional[NamedSharding]:
    if _MESH is None:
        return None
    return NamedSharding(_MESH, filter_spec(P(*entries), _MESH))
