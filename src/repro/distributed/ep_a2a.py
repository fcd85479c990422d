"""Explicit expert-parallel MoE dispatch: shard_map + lax.all_to_all.

The §Perf hillclimb established that GSPMD cannot derive an efficient
program for cross-device expert dispatch from sharding annotations alone
(it all-gathers the expert buffers; EXPERIMENTS §Perf cell 2, iters 2/4/5).
This module is the explicit-collective answer — the DeepSpeed-MoE pattern
on jax-native primitives:

    per device:  route local tokens → per-target-expert-shard buffers
    all_to_all:  exchange buffers over the expert axis  (tokens → owners)
    local:       dense expert FFN on owned experts
    all_to_all:  send results back
    per device:  weighted combine

Works under ``shard_map`` over an ``("expert",)`` (sub-)mesh axis, with the
batch sharded over the remaining axes by GSPMD as usual.  Capacity is per
(source device × target device) so the exchanged buffers are statically
shaped, as ``lax.all_to_all`` requires.

This is a validated prototype wired for e.g. grok (8 experts over an
8-wide axis); integrating it behind ``moe_ffn`` for the full train step is
the documented next step, with the bubble planner already emitting the
expert placement it consumes.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def make_ep_ffn(mesh: Mesh, axis: str, n_experts: int, top_k: int,
                ffn_apply: Callable, cap_per_pair: int):
    """Build an expert-parallel FFN: (params_local, x_local) -> y_local.

    ``ffn_apply(wi, wg, wo, buf)``: dense per-expert FFN on (E_loc, C, D).
    ``cap_per_pair``: token capacity per (src shard, dst shard, local
    expert) — static all_to_all shape.
    """
    n_shards = mesh.shape[axis]
    assert n_experts % n_shards == 0
    e_loc = n_experts // n_shards

    def ep_ffn(wi, wg, wo, x, gate_idx, gate_vals):
        """Per-shard body (runs under shard_map).

        wi/wg/wo: (E_loc, ...) local expert weights.
        x: (T, D) local tokens; gate_idx/vals: (T, K) global expert ids.
        """
        T, D = x.shape
        K = gate_idx.shape[1]
        TK = T * K
        C = cap_per_pair

        flat_e = gate_idx.reshape(TK)                   # global expert id
        dst = flat_e // e_loc                           # target shard
        le = flat_e % e_loc                             # local expert there
        # rank within (dst, le) group, gather-only:
        key = dst * e_loc + le
        order = jnp.argsort(key)
        key_sorted = key[order]
        starts = jnp.searchsorted(key_sorted, jnp.arange(n_shards * e_loc),
                                  side="left")
        ends = jnp.searchsorted(key_sorted, jnp.arange(n_shards * e_loc),
                                side="right")
        idx = starts[:, None] + jnp.arange(C)[None]     # (S*E_loc, C)
        valid = idx < ends[:, None]
        idx = jnp.minimum(idx, TK - 1)
        src_assign = jnp.take_along_axis(
            jnp.broadcast_to(order[None], (n_shards * e_loc, TK)), idx,
            axis=1)                                     # assignment index
        src_tok = src_assign // K
        sbuf = x[src_tok.reshape(-1)].reshape(n_shards, e_loc * C, D)
        sbuf = sbuf * valid.reshape(n_shards, e_loc * C, 1).astype(x.dtype)

        # exchange: dim0 = shard axis
        rbuf = jax.lax.all_to_all(sbuf, axis, 0, 0, tiled=False)
        # rbuf: (n_shards, e_loc*C, D) — tokens from every source shard
        rbuf = rbuf.reshape(n_shards, e_loc, C, D).transpose(1, 0, 2, 3) \
            .reshape(e_loc, n_shards * C, D)

        out = ffn_apply(wi, wg, wo, rbuf)               # (e_loc, S*C, D)

        # send back
        out = out.reshape(e_loc, n_shards, C, D).transpose(1, 0, 2, 3) \
            .reshape(n_shards, e_loc * C, D)
        back = jax.lax.all_to_all(out, axis, 0, 0, tiled=False)
        # back[s, e*C + c] = result for the token we packed at (s, e, c)

        # combine: invert the packing (gather-only)
        inv = jnp.argsort(order)
        pos_sorted = jnp.arange(TK) - jnp.take(starts, key_sorted)
        pos = jnp.take(pos_sorted, inv)                 # (TK,)
        kept = pos < C
        rows = jnp.where(kept, dst * (e_loc * C) + le * C + pos, 0)
        flat = back.reshape(n_shards * e_loc * C, D)
        got = flat[rows]                                # (TK, D)
        w = (gate_vals.reshape(TK) * kept).astype(x.dtype)
        y = (got * w[:, None]).reshape(T, K, D).sum(axis=1)
        return y

    # shard_map wrapper: tokens replicated per expert-shard? No — tokens are
    # sharded over the OTHER axes by the caller; over `axis` each shard
    # holds a distinct slice of the batch (standard EP: batch × expert grid)
    pspec_w = P(axis)            # expert-sharded weights (E dim leading)
    pspec_x = P(axis)            # batch slice per expert shard
    f = jax.shard_map(ep_ffn, mesh=mesh,
                      in_specs=(pspec_w, pspec_w, pspec_w, pspec_x, pspec_x,
                                pspec_x),
                      out_specs=pspec_x,
                      check_vma=False)
    return f
