"""Weights of a cell's functions, drawn on the device from the run's seed.

The leaves every function shares are drawn in one jitted call and copied to
host memory once; the one leaf that tells functions apart (the family's
``DISTINCT_LEAF``) is drawn per function by a second jitted call. Each
function's host tree holds the shared arrays and its own leaf, and the
program still loads the whole tree into HBM for each function.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp

from schedules import derive_seed

Shapes = Dict[str, Any]  # nested dict whose leaves are (shape, dtype)


def leaves(shapes: Shapes, prefix: Tuple[str, ...] = ()):
    """(path, shape, dtype) of every leaf, in a fixed order."""
    for k in sorted(shapes):
        v = shapes[k]
        if isinstance(v, dict):
            yield from leaves(v, prefix + (k,))
        else:
            yield prefix + (k,), tuple(v[0]), jnp.dtype(v[1])


def _nest(flat: Dict[Tuple[str, ...], Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for path, x in flat.items():
        d = out
        for k in path[:-1]:
            d = d.setdefault(k, {})
        d[path[-1]] = x
    return out


def check_layout(shapes: Shapes, program_tree) -> None:
    """Raise unless the program's parameter tree (``jax.eval_shape`` of
    its init) has exactly the paths, shapes and dtypes the family states."""
    got = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(program_tree)[0]:
        key = tuple(p.key for p in path)
        got[key] = (tuple(leaf.shape), jnp.dtype(leaf.dtype))
    want = {p: (s, d) for p, s, d in leaves(shapes)}
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))
        raise ValueError(f"program parameter layout departs: {diff[:6]}")


def make(shapes: Shapes, draw: Callable, distinct: Tuple[str, ...],
         seed: int, functions: int) -> List[Dict[str, Any]]:
    """Host (numpy) trees of ``functions`` functions, from ``seed``."""
    spec = list(leaves(shapes))
    shared = [(p, s, d) for p, s, d in spec if p != distinct]
    ((_, ds, dd),) = [(p, s, d) for p, s, d in spec if p == distinct]

    @jax.jit
    def draw_shared(key):
        return [draw(p, s, jax.random.fold_in(key, i)).astype(d)
                for i, (p, s, d) in enumerate(shared)]

    @jax.jit
    def draw_distinct(key):
        return draw(distinct, ds, key).astype(dd)

    base = jax.device_get(draw_shared(jax.random.PRNGKey(derive_seed(seed, 3))))
    flat = {p: x for (p, _, _), x in zip(shared, base)}
    trees = []
    for f in range(functions):
        own = jax.device_get(
            draw_distinct(jax.random.PRNGKey(derive_seed(seed, 4, f))))
        trees.append(_nest({**flat, distinct: own}))
    return trees
