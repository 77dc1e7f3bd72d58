"""jaxpr-level checks: properties the AST passes cannot decide statically.

These helpers trace a function (abstractly — no FLOPs run) and inspect the
resulting jaxpr, complementing the AST passes:

  * ``collective_axis_names`` — every named axis appearing in collective
    equations (``psum``/``all_gather``/``shard_map``...), recursing into
    closed subjaxprs. Cross-checked against a mesh's declared axes by
    ``undeclared_collective_axes``.
  * ``host_callback_primitives`` — callback/debug primitives reachable
    from traced code (``pure_callback``, ``io_callback``,
    ``debug_callback``): each is a host round-trip per step.
  * ``integer_cotangent_violations`` — runs the real VJP and verifies the
    float0/None cotangent contract for integer/bool primals (the bug class
    the custom-VJP AST pass can only check arity for).
"""

from __future__ import annotations

from typing import Iterable, List, Sequence, Set

import jax
import jax.numpy as jnp

_CALLBACK_PRIMITIVES = {"pure_callback", "io_callback", "debug_callback",
                        "debug_print", "outside_call"}


def iter_eqns(jaxpr) -> Iterable:
    """All equations of ``jaxpr``, recursing into closed subjaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", None)
            if sub is not None:
                yield from iter_eqns(sub)
            elif isinstance(v, (list, tuple)):
                for item in v:
                    sub = getattr(item, "jaxpr", None)
                    if sub is not None:
                        yield from iter_eqns(sub)


def _axis_strings(value) -> Set[str]:
    if isinstance(value, str):
        return {value}
    if isinstance(value, (list, tuple, set, frozenset)):
        out: Set[str] = set()
        for v in value:
            out |= _axis_strings(v)
        return out
    return set()


def collective_axis_names(fn, *args, **kwargs) -> Set[str]:
    """Named axes referenced by collectives in ``fn``'s jaxpr."""
    jaxpr = jax.make_jaxpr(fn, **kwargs)(*args).jaxpr
    axes: Set[str] = set()
    for eqn in iter_eqns(jaxpr):
        for key in ("axes", "axis_name", "axis_names"):
            if key in eqn.params:
                axes |= _axis_strings(eqn.params[key])
        mesh = eqn.params.get("mesh")
        if mesh is not None and hasattr(mesh, "axis_names"):
            # shard_map in/out specs reference these; the mesh itself
            # declares them, so they are not "uses" — skip.
            pass
    return axes


def undeclared_collective_axes(fn, declared: Sequence[str],
                               *args) -> Set[str]:
    """Collective axes in ``fn``'s jaxpr that ``declared`` does not cover."""
    return collective_axis_names(fn, *args) - set(declared)


def host_callback_primitives(fn, *args) -> List[str]:
    """Names of host-callback primitives reachable from ``fn``'s jaxpr."""
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    return [eqn.primitive.name for eqn in iter_eqns(jaxpr)
            if eqn.primitive.name in _CALLBACK_PRIMITIVES]


def integer_cotangent_violations(fn, *primals) -> List[int]:
    """Argument indices whose cotangent violates the float0 contract.

    Runs ``jax.vjp(fn, *primals)`` with a ones-like output cotangent. For
    every integer/bool primal, the returned cotangent must have dtype
    ``float0`` (the "no gradient" dtype) — anything else means the custom
    VJP invents gradients for non-differentiable inputs. Raises whatever
    the VJP itself raises (a wrong-arity bwd fails here too)."""
    out, vjp_fn = jax.vjp(fn, *primals)
    cts = vjp_fn(jax.tree.map(jnp.ones_like, out))
    bad: List[int] = []
    for i, (p, ct) in enumerate(zip(primals, cts)):
        leaves = jax.tree.leaves(p)
        ct_leaves = jax.tree.leaves(ct)
        if not leaves or not ct_leaves:
            continue
        if all(jnp.issubdtype(jnp.asarray(l).dtype, jnp.integer)
               or jnp.asarray(l).dtype == jnp.bool_ for l in leaves):
            if any(c.dtype != jax.dtypes.float0 for c in ct_leaves):
                bad.append(i)
    return bad
