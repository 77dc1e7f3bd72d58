"""The benchmark's weights: made from the seed, on the device, in one jitted
call, in the layout and type the program serves them in.

A configuration describes its parameters as a nested dict of leaves
``(shape, dtype, init, scale)``:

  * ``"fanin"`` — normal * scale / sqrt(fan-in), the fan-in being every
    axis but the last (less a leading stack axis, ``stack=True``);
  * ``"normal"`` — normal * scale;
  * ``"one"`` — 1 + normal * scale (norm gains).

Both the harness (for the program) and the plain references (for
themselves) call ``make`` with the seed's key, so neither takes the other's
weights.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp


def freeze(layout, prefix=()) -> Tuple:
    """Nested dict of leaf specs -> hashable sorted tuple of (path, spec)."""
    out = []
    for name in sorted(layout):
        v = layout[name]
        if isinstance(v, dict):
            out.extend(freeze(v, prefix + (name,)))
        else:
            out.append((prefix + (name,), tuple(v)))
    return tuple(out)


@functools.partial(jax.jit, static_argnums=0)
def make(frozen, key):
    tree: Dict[str, Any] = {}
    for i, (path, spec) in enumerate(frozen):
        shape, dtype, init, scale = spec[:4]
        stack = len(spec) > 4 and spec[4]
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if init == "fanin":
            fan = math.prod(shape[1 if stack else 0:-1])
            x = x * (scale / math.sqrt(fan))
        elif init == "normal":
            x = x * scale
        elif init == "one":
            x = 1.0 + x * scale
        else:
            raise ValueError(f"unknown init {init!r} at {path}")
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = x.astype(dtype)
    return tree


def leaf_paths(tree) -> list:
    return ["/".join(str(getattr(k, "key", k)) for k in p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def leaf_norms(tree) -> Dict[str, jax.Array]:
    """Per-leaf L2 norms in float32, keyed by path."""
    leaves = jax.tree.leaves(tree)
    return {p: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for p, x in zip(leaf_paths(tree), leaves)}


def diff_norms(a, b) -> Dict[str, jax.Array]:
    """Per-leaf L2 norms of a - b in float32."""
    return leaf_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))


def check_layout(made, program) -> None:
    """The benchmark's weights must match the program's own parameter tree
    (structure, shapes, dtypes) exactly."""
    got = {p: (tuple(x.shape), str(x.dtype))
           for p, x in zip(leaf_paths(made), jax.tree.leaves(made))}
    want = {p: (tuple(x.shape), str(x.dtype))
            for p, x in zip(leaf_paths(program), jax.tree.leaves(program))}
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))
        raise ValueError(f"parameter layout differs from the program's: "
                         f"{diff[:6]}")
