"""Pallas TPU kernel for the RG-LRU linear recurrence h_t = a_t·h_{t-1} + b_t.

TPU adaptation: the recurrence is diagonal, so the state is a (N,) vector
per batch row.  The sequence is chunked; the chunk axis is the innermost
grid dimension (sequential on TPU), with the running state carried in VMEM
scratch — HBM traffic is exactly one read of (a, b) and one write of h, the
memory-bound optimum.  Within a chunk the time loop runs in VREGs over the
VMEM-resident tile; the feature axis N (lane-aligned, multiples of 128)
vectorises on the VPU.

Validated in interpret mode against the associative-scan oracle in
``ref.lru_scan_ref``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(a_ref, b_ref, h_ref, carry_ref, *, chunk: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    # rows are read and written through the refs: indexing a loaded value
    # at a traced ``t`` lowers to ``dynamic_slice``, which Mosaic lacks
    def step(t, h):                            # h: (1, N)
        row = pl.ds(t, 1)
        h = (a_ref[0, row, :].astype(jnp.float32) * h
             + b_ref[0, row, :].astype(jnp.float32))
        h_ref[0, row, :] = h.astype(h_ref.dtype)
        return h

    carry_ref[...] = jax.lax.fori_loop(0, chunk, step, carry_ref[...])


def lru_scan(a, b, *, chunk: int = 256, interpret: Optional[bool] = None):
    """a, b: (B, S, N) → h: (B, S, N) (fp32 state math)."""
    B, S, N = a.shape
    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2
    nc = S // chunk
    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    from jax.experimental.pallas import tpu as pltpu
    kern = functools.partial(_kernel, chunk=chunk)
    return pl.pallas_call(
        kern,
        grid=(B, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, N), lambda ib, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, N), lambda ib, ic: (ib, ic, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, N), lambda ib, ic: (ib, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, N), jnp.float32)],
        interpret=interpret,
    )(a, b)
