"""Weights from the seed, drawn the same way for the program and for the
plain references.

Every leaf of the program's parameter tree is drawn from a key made of
the seed and the leaf's path; a leaf stacked over layers (the program
scans its layers, so ``stage<i>/...`` leaves carry the layer on axis 0)
draws layer ``l`` from that key folded with ``l``.  So the program's
whole tree is one jitted call, and a reference rebuilds any one layer
from the seed alone, without anything the program made.

Values are uniform with the rule's standard deviation (or its bounds);
the rules live with each family's reference, which knows what the
leaves mean.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np


def base_key(seed: int) -> jax.Array:
    """A key from any whole number up to 64 bits."""
    k = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32((seed >> 32) & 0xFFFFFFFF))


def leaf_key(base: jax.Array, path: str) -> jax.Array:
    return jax.random.fold_in(base, np.uint32(zlib.crc32(path.encode())))


def draw(key, shape, dtype, rule) -> jax.Array:
    """``rule`` is ``("std", s)``: uniform on [-s*sqrt(3), s*sqrt(3)];
    or ``("range", lo, hi)``: uniform on [lo, hi)."""
    if rule[0] == "std":
        a = rule[1] * math.sqrt(3.0)
        lo, hi = -a, a
    else:
        _, lo, hi = rule
    return jax.random.uniform(key, shape, jnp.float32, lo, hi).astype(dtype)


def is_stacked(path: str) -> bool:
    return path.startswith("stage")


def path_of(kp) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in kp)


def make_params(spec_tree, seed: int, rule):
    """The program's parameter tree (``spec_tree`` of ShapeDtypeStructs),
    drawn on the device in one jitted call, in the served dtypes."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(spec_tree)
    paths = [path_of(kp) for kp, _ in flat]
    specs = [s for _, s in flat]

    def build(base):
        out = []
        for p, s in zip(paths, specs):
            k = leaf_key(base, p)
            if is_stacked(p):
                r = rule(p, s.shape[1:])
                out.append(jax.vmap(lambda l, k=k, s=s, r=r: draw(
                    jax.random.fold_in(k, l), s.shape[1:], s.dtype, r))(
                        jnp.arange(s.shape[0], dtype=jnp.uint32)))
            else:
                out.append(draw(k, s.shape, s.dtype, rule(p, s.shape)))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(base_key(seed))


def layer_leaf(base, path: str, layer: int, shape, dtype, rule):
    """One layer of a stacked leaf, as ``make_params`` drew it."""
    k = jax.random.fold_in(leaf_key(base, path), layer)
    return draw(k, shape, dtype, rule)
