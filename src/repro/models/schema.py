"""Parameter schema: define each tensor once — shape, logical dims, init.

Every model parameter is declared as a :class:`ParamDef`; the same
declaration yields (a) the initialised array, (b) the logical-dim annotation
consumed by ``distributed.sharding`` (which intersects it with the planner's
:class:`repro.core.planner.Plan`), and (c) the ShapeDtypeStruct used by the
dry-run.  Keeping one source of truth prevents shape/spec drift.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

Initializer = Callable[[jax.Array, tuple[int, ...], Any], jax.Array]


def normal(stddev: float = 0.02) -> Initializer:
    def init(key, shape, dtype):
        x = jax.random.normal(key, shape, jnp.float32)
        # a no-op on f32 that XLA keeps: it stops the compiler folding
        # ``stddev`` into the normal's own sqrt(2) factor under jit, so the
        # jitted draw rounds exactly as the op-by-op one does
        x = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=23)
        return (x * stddev).astype(dtype)
    return init


def zeros() -> Initializer:
    def init(key, shape, dtype):
        return jnp.zeros(shape, dtype)
    return init


def ones() -> Initializer:
    def init(key, shape, dtype):
        return jnp.ones(shape, dtype)
    return init


def uniform_range(lo: float, hi: float) -> Initializer:
    def init(key, shape, dtype):
        u = jax.random.uniform(key, shape, jnp.float32, lo, hi)
        return u.astype(dtype)
    return init


@dataclasses.dataclass(frozen=True)
class ParamDef:
    """One parameter tensor: shape + logical dims + initializer."""

    shape: tuple[int, ...]
    dims: tuple[Optional[str], ...]     # logical dim name per axis (or None)
    init: Initializer = normal()
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        assert len(self.shape) == len(self.dims), (self.shape, self.dims)


Schema = dict  # nested dict[str, ParamDef | Schema]


def init_params(schema: Schema, key: jax.Array) -> dict:
    """Instantiate every ParamDef with a derived PRNG key."""
    flat: list[tuple[tuple[str, ...], ParamDef]] = []

    def walk(node, path):
        if isinstance(node, ParamDef):
            flat.append((path, node))
        else:
            for k, v in sorted(node.items()):
                walk(v, path + (k,))

    walk(schema, ())
    keys = jax.random.split(key, max(len(flat), 1))
    out: dict = {}
    for (path, pd), k in zip(flat, keys):
        cur = out
        for p in path[:-1]:
            cur = cur.setdefault(p, {})
        cur[path[-1]] = pd.init(k, pd.shape, pd.dtype)
    return out


def param_dims(schema: Schema) -> dict:
    """Same tree, values = logical-dim tuples (for the sharding layer)."""
    if isinstance(schema, ParamDef):
        return schema.dims
    return {k: param_dims(v) for k, v in schema.items()}


def param_shapes(schema: Schema) -> dict:
    """Same tree, values = ShapeDtypeStruct (for dry-run, no allocation)."""
    if isinstance(schema, ParamDef):
        return jax.ShapeDtypeStruct(schema.shape, schema.dtype)
    return {k: param_shapes(v) for k, v in schema.items()}


def n_params(schema: Schema) -> int:
    if isinstance(schema, ParamDef):
        n = 1
        for s in schema.shape:
            n *= s
        return n
    return sum(n_params(v) for v in schema.values())


def stacked(pd: ParamDef, n: int, dim: str = "layers") -> ParamDef:
    """Add a leading layer-stack axis (for lax.scan over layers)."""
    return dataclasses.replace(pd, shape=(n,) + pd.shape,
                               dims=(dim,) + pd.dims)


def map_schema(fn: Callable[[ParamDef], ParamDef], schema: Schema) -> Schema:
    if isinstance(schema, ParamDef):
        return fn(schema)
    return {k: map_schema(fn, v) for k, v in schema.items()}
