"""Operations and bytes of the served model's steps, from the
configuration's sizes alone (the program's ``program`` block).

Counted per block kind of ``block_pattern``:

* ``attn``: q, k, v, o projections (grouped-query) and a SwiGLU MLP; the
  attention itself costs ``4 * n_heads * head_dim`` per position attended
  (scores and weighted sum);
* ``rwkv``: the r, k, v, g, o projections, the decay's rank-64 pair, the
  channel mix's key, value and receptance; the wkv recurrence costs
  ``4 * head_dim`` per element of each head's ``head_dim x head_dim``
  state (read, decay, outer product, add).

A matmul of ``n`` weights costs ``2 n`` operations per token.  Norms,
activations and other elementwise work are left out: they are small and
no kernel's bound.  Bytes are what a decode step must move at least:
every weight once, the embedding rows it gathers, and per live sequence
its KV (read up to its length, one position written) or its recurrent
state (read and written).
"""

from __future__ import annotations

LORA = 64           # the rwkv decay's rank (the program's rwkv6_schema)


def head_dim(cfg: dict) -> int:
    if cfg.get("head_dim"):
        return int(cfg["head_dim"])
    return cfg["d_model"] // cfg["n_heads"]


def kinds(cfg: dict) -> list[str]:
    pat = list(cfg.get("block_pattern", ["attn"]))
    return [pat[i % len(pat)] for i in range(cfg["n_layers"])]


def layer_matmul_weights(cfg: dict, kind: str) -> int:
    d, f = cfg["d_model"], cfg["d_ff"]
    if kind == "attn":
        H, K, hd = cfg["n_heads"], cfg["n_kv_heads"], head_dim(cfg)
        return d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * f
    if kind == "rwkv":
        return 5 * d * d + 2 * d * LORA + 2 * d * f + d * d
    raise KeyError(f"flops: no count for block kind {kind!r}")


def layer_other_weight_bytes(cfg: dict, kind: str) -> int:
    """Weights that are not matmuls: norms, mixes, decay base, bonus."""
    d = cfg["d_model"]
    if kind == "attn":
        return 2 * d * 2
    if kind == "rwkv":
        H = d // head_dim(cfg)
        return 2 * d * 2 + 7 * d * 2 + d * 4 + H * head_dim(cfg) * 4 + d * 4
    raise KeyError(kind)


def mixer_flops(cfg: dict, kind: str, attended: int) -> int:
    """The sequence mixer's own work for one token attending to
    ``attended`` positions (itself included)."""
    if kind == "attn":
        return 4 * cfg["n_heads"] * head_dim(cfg) * attended
    if kind == "rwkv":
        hd = head_dim(cfg)
        return 4 * cfg["d_model"] * hd
    raise KeyError(kind)


def matmul_weights(cfg: dict, head: bool = True) -> int:
    n = sum(layer_matmul_weights(cfg, k) for k in kinds(cfg))
    return n + (cfg["d_model"] * cfg["vocab"] if head else 0)


def decode_flops(cfg: dict, ctx: list[int]) -> int:
    """One decode step of the live sequences; ``ctx[i]`` tokens precede
    sequence ``i``'s new token."""
    per = 2 * matmul_weights(cfg)
    total = 0
    for c in ctx:
        total += per + sum(mixer_flops(cfg, k, c + 1) for k in kinds(cfg))
    return total


def prefill_flops(cfg: dict, length: int) -> int:
    """One prompt of ``length`` tokens, causal, logits at its last."""
    body = 2 * matmul_weights(cfg, head=False) * length
    mix = 0
    for k in kinds(cfg):
        if k == "attn":
            mix += 4 * cfg["n_heads"] * head_dim(cfg) \
                * length * (length + 1) // 2
        else:
            mix += mixer_flops(cfg, k, 0) * length
    return body + mix + 2 * cfg["d_model"] * cfg["vocab"]


def weight_bytes(cfg: dict) -> int:
    """Every weight a decode step reads once (bf16 matmuls), without the
    embedding table, of which it gathers only its rows."""
    n = sum(2 * layer_matmul_weights(cfg, k)
            + layer_other_weight_bytes(cfg, k) for k in kinds(cfg))
    return n + 2 * cfg["d_model"] * cfg["vocab"] + 2 * cfg["d_model"]


def state_bytes(cfg: dict, kind: str, ctx: int) -> int:
    """What one live sequence's state costs a decode step in one layer."""
    if kind == "attn":
        per_pos = 2 * cfg["n_kv_heads"] * head_dim(cfg) * 2   # k and v, bf16
        return per_pos * ctx + per_pos                        # read, write
    if kind == "rwkv":
        hd = head_dim(cfg)
        wkv = cfg["d_model"] * hd * 4                          # f32 state
        shifts = 2 * cfg["d_model"] * 2
        return 2 * (wkv + shifts)                              # read, write
    raise KeyError(kind)


def decode_bytes(cfg: dict, ctx: list[int]) -> int:
    total = weight_bytes(cfg) + 2 * cfg["d_model"] * len(ctx)
    for c in ctx:
        total += sum(state_bytes(cfg, k, c) for k in kinds(cfg))
    return total


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline: the larger of the compute and the memory bound."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
