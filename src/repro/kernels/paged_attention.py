"""Pallas TPU paged decode attention: K/V read through a block table.

The serving engine stores each slot's KV in fixed-size *pages* of a shared
pool — ``(num_pages, page_size, K, hd)`` — and a per-slot *block table* of
page indices.  A KV migration (steal, park/splice, rebalance) is then a
block-table edit: no tensor moves, the pages stay where they are.  This
kernel is the decode path that makes that layout free to read: one query
token per slot attends over its pages by indexing the pool through the
scalar-prefetched block table.

Structure follows ``flash_attention._kernel`` (the online-softmax VMEM
scratch pattern): the page axis is the innermost grid dimension, iterated
sequentially per slot, so (m, l, acc) carry across pages.  The block
table and per-slot lengths ride in scalar-prefetch memory
(``PrefetchScalarGridSpec``) because the K/V BlockSpec index map *is* the
table lookup — the DMA for page ``i`` of slot ``b`` fetches pool page
``tables[b, i]``.

Layout: q ``(B, K, g, hd)`` (GQA groups folded out of H = K*g), pools
``(num_pages, page_size, K, hd)``, tables ``(B, pages_per_slot)`` int32,
lengths ``(B,)`` int32 — the number of valid tokens *including* the one
just written; the query is the token at position ``lengths - 1``.  Unused
table entries must be 0: page 0 is the engine's trash page, never valid,
and masked off by the length test.

Tiling: Mosaic requires the last two dims of every block to be multiples
of the dtype's (sublane, lane) tile — (8, 128) for f32, (16, 128) for
bf16 — or equal to the array's own dims.  A per-head block
``(1, page_size, 1, hd)`` fails that on the head axis, so the kernel sees
each pool as ``(num_pages, page_size, K*hd)`` (a free reshape of the
contiguous trailing dims) and fetches one whole page — all KV heads — per
grid step: the block's last two dims are then the array's, legal for any
``page_size``.  Each head is a static lane slice of the page.  For tile-
aligned VMEM use ``page_size`` should still be a sublane multiple (16 for
bf16) and ``hd`` a multiple of 128.  Validated against ``ref.sdpa_ref`` /
``ref.paged_sdpa_ref`` in interpret mode by
``tests/test_paged_attention.py``; compiled for a described v5e by
``tests/test_tpu_compile.py``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(tables_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, scale: float,
            window: Optional[int], page_size: int, npages: int):
    b = pl.program_id(0)
    ip = pl.program_id(1)

    @pl.when(ip == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # position of each pool column = page rank * page_size + offset; valid
    # while < lengths[b] (and, for SWA, within `window` of the query).  A
    # page past the slot's used count points at the trash page — every one
    # of its positions fails the length test, so its contents never leak.
    _, K, g, hd = q_ref.shape
    length = len_ref[b]
    k_pos = ip * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (g, page_size), 1)
    mask = k_pos < length
    if window is not None:
        mask &= k_pos > (length - 1 - window)

    for h in range(K):                                   # static: K is small
        q = q_ref[0, h].astype(jnp.float32) * scale      # (g, hd)
        k = k_ref[0, :, h * hd:(h + 1) * hd].astype(jnp.float32)  # (ps, hd)
        v = v_ref[0, :, h * hd:(h + 1) * hd].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (g, ps)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[h]                                # (g, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                           # (g, ps)
        l_ref[h] = l_ref[h] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(ip == npages - 1)
    def _done():
        for h in range(K):
            l = jnp.maximum(l_ref[h], 1e-30)
            o_ref[0, h] = (acc_ref[h] / l).astype(o_ref.dtype)


def paged_attn(q, k_pool, v_pool, tables, lengths, *,
               window: Optional[int] = None, scale: float = 1.0,
               interpret: Optional[bool] = None):
    """One decode step of paged attention.

    q ``(B, K, g, hd)``, pools ``(P, page_size, K, hd)``, tables
    ``(B, pages_per_slot)`` int32, lengths ``(B,)`` int32.  Returns
    ``(B, K, g, hd)``.  Rows with ``lengths == 0`` (free slots) produce
    finite garbage — callers discard them, exactly like the dense path.
    """
    B, K, g, hd = q.shape
    P, page_size, Kp, hdp = k_pool.shape
    assert (Kp, hdp) == (K, hd), (k_pool.shape, q.shape)
    assert v_pool.shape == k_pool.shape
    npages = tables.shape[1]
    assert tables.shape == (B, npages) and lengths.shape == (B,)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    from jax.experimental.pallas import tpu as pltpu

    kern = functools.partial(_kernel, scale=scale, window=window,
                             page_size=page_size, npages=npages)
    page = pl.BlockSpec((1, page_size, K * hd),
                        lambda b, ip, tbl, ln: (tbl[b, ip], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, npages),
        in_specs=[
            pl.BlockSpec((1, K, g, hd), lambda b, ip, tbl, ln: (b, 0, 0, 0)),
            page, page,
        ],
        out_specs=pl.BlockSpec((1, K, g, hd),
                               lambda b, ip, tbl, ln: (b, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((K, g, hd), jnp.float32),
                        pltpu.VMEM((K, g, 1), jnp.float32),
                        pltpu.VMEM((K, g, 1), jnp.float32)],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, g, hd), q.dtype),
        interpret=interpret,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_pool.reshape(P, page_size, K * hd),
      v_pool.reshape(P, page_size, K * hd))
