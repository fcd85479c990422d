"""Pallas TPU kernel for RWKV6 (Finch) WKV with data-dependent decay.

Per head the state is an (hd, hd) matrix S with the recurrence
    y_t = r_t · (S + u ⊙ k_t v_tᵀ),      S ← diag(w_t) S + k_t v_tᵀ.

TPU adaptation: grid (B, H, chunks) with the chunk axis innermost
(sequential), S carried in VMEM scratch (hd×hd = 64×64 fp32 = 16 KiB —
comfortably VMEM-resident).  The inner time loop forms rank-1 updates in
VREGs; r/k/v/w chunk tiles stream HBM→VMEM once.  The final state is
emitted so prefill hands off to decode.

Layout for Mosaic: inputs are transposed outside the kernel so every
block's last two dims are tile multiples or whole array dims — ``v`` and
``y`` as ``(B, H, S, hd)`` (rows ``(1, hd)``), ``r``/``k``/``w`` as
``(B, H, hd, S)`` (columns ``(hd, 1)``), ``u`` as ``(H, hd, 1)``.  The
time step ``t`` is traced, so a row is read and written through the ref
(``pl.ds``); a column is picked out of the loaded tile with a lane mask
and a sum, which is exact (one term is non-zero) and needs no dynamic
lane slice.

Validated in interpret mode against the lax.scan oracle ``ref.wkv_ref``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(rT_ref, kT_ref, v_ref, wT_ref, u_ref, y_ref, sfin_ref, s_ref,
            *, chunk: int, nc: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    rT = rT_ref[0, 0].astype(jnp.float32)      # (hd, chunk)
    kT = kT_ref[0, 0].astype(jnp.float32)
    wT = wT_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)           # (hd, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, rT.shape, 1)

    def col(xT, t):                            # column t of a tile: (hd, 1)
        return jnp.where(lane == t, xT, 0.0).sum(axis=1, keepdims=True)

    def step(t, S):                            # S: (hd, hd)
        row = pl.ds(t, 1)
        r, k, w = col(rT, t), col(kT, t), col(wT, t)
        v = v_ref[0, 0, row, :].astype(jnp.float32)             # (1, hd)
        kv = k * v                                               # rank-1
        y_ref[0, 0, row, :] = ((S + u * kv) * r).sum(
            axis=0, keepdims=True).astype(y_ref.dtype)           # (1, hd)
        return w * S + kv

    S = jax.lax.fori_loop(0, chunk, step, s_ref[...])
    s_ref[...] = S

    @pl.when(ic == nc - 1)
    def _done():
        sfin_ref[0, 0] = S.astype(sfin_ref.dtype)


def wkv(r, k, v, w, u, *, chunk: int = 128,
        interpret: Optional[bool] = None):
    """r,k,v,w: (B,S,H,hd); u: (H,hd) → (y (B,S,H,hd) f32, S_final
    (B,H,hd,hd) f32)."""
    B, S, H, hd = r.shape
    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2
    nc = S // chunk
    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    from jax.experimental.pallas import tpu as pltpu
    kern = functools.partial(_kernel, chunk=chunk, nc=nc)
    cols = pl.BlockSpec((1, 1, hd, chunk), lambda ib, ih, ic: (ib, ih, 0, ic))
    rows = pl.BlockSpec((1, 1, chunk, hd), lambda ib, ih, ic: (ib, ih, ic, 0))
    y, sfin = pl.pallas_call(
        kern,
        grid=(B, H, nc),
        in_specs=[cols, cols, rows, cols,
                  pl.BlockSpec((1, hd, 1), lambda ib, ih, ic: (ih, 0, 0))],
        out_specs=[
            rows,
            pl.BlockSpec((1, 1, hd, hd), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(r.transpose(0, 2, 3, 1), k.transpose(0, 2, 3, 1),
      v.transpose(0, 2, 1, 3), w.transpose(0, 2, 3, 1), u[:, :, None])
    return y.transpose(0, 2, 1, 3), sfin
