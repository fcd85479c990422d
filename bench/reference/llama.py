"""Plain float32 forward of a dense GQA decoder (Llama family: Yi-6B).

Follows the published Llama block: RMSNorm, grouped-query attention with
rotary positions (rotate-half, the two halves of each head), a SwiGLU
MLP, a final RMSNorm and an untied output matrix.  Where the served
program's variant differs, this follows the program, since the weights
are the program's: the embedding is scaled by sqrt(d_model); each norm
multiplies by ``1 + scale``; the norm's epsilon is 1e-6 (Yi's
config.json says 1e-5, a difference far below bfloat16's rounding).
No cache, no batching, no kernels: one sequence at a time, full causal
attention over it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.common import Walk, bucket, mm, rmsnorm, sequences

EPS = 1e-6
STAGE = "stage0/b0_attn/"


def rule(path: str, shape):
    """How each leaf is drawn: sizes keep every layer's output near unit
    scale, so each of the 32 layers moves the result."""
    name = path.rsplit("/", 1)[-1]
    if name == "scale":
        return ("std", 0.1)
    if path == "lm_head/w":
        return ("std", 1.0 / math.sqrt(shape[0]))
    if path == "embed/table":
        return ("std", 1.0 / math.sqrt(shape[1]))
    if path.endswith("attn/wo"):
        return ("std", 1.0 / math.sqrt(shape[0] * shape[1]))
    return ("std", 1.0 / math.sqrt(shape[0]))


def specs(cfg: dict):
    d, H, K, f, V = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                     cfg["d_ff"], cfg["vocab"])
    hd = cfg.get("head_dim") or d // H
    bf = jnp.dtype(cfg.get("param_dtype", "bfloat16"))
    top = {"embed/table": ((V, d), bf), "final_norm/scale": ((d,), bf),
           "lm_head/w": ((d, V), bf)}
    layer = {STAGE + "ln1/scale": ((d,), bf),
             STAGE + "attn/wq": ((d, H, hd), bf),
             STAGE + "attn/wk": ((d, K, hd), bf),
             STAGE + "attn/wv": ((d, K, hd), bf),
             STAGE + "attn/wo": ((H, hd, d), bf),
             STAGE + "ln2/scale": ((d,), bf),
             STAGE + "ffn/wi": ((d, f), bf),
             STAGE + "ffn/wg": ((d, f), bf),
             STAGE + "ffn/wo": ((f, d), bf)}
    return top, layer


def rope(x, theta):
    """x (S, heads, hd): rotate the two halves of each head by position."""
    S, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def make_layer(cfg: dict):
    d, H, K = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    hd = cfg.get("head_dim") or d // H
    theta = float(cfg.get("rope_theta", 10000.0))

    def layer(w, h, control):
        g = lambda n: w[STAGE + n]
        S = h.shape[0]
        x = rmsnorm(h, g("ln1/scale"), EPS)
        q = mm(x, g("attn/wq").reshape(d, H * hd), control).reshape(S, H, hd)
        k = mm(x, g("attn/wk").reshape(d, K * hd), control).reshape(S, K, hd)
        v = mm(x, g("attn/wv").reshape(d, K * hd), control).reshape(S, K, hd)
        q, k = rope(q, theta), rope(k, theta)
        k = jnp.repeat(k, H // K, axis=1)
        v = jnp.repeat(v, H // K, axis=1)
        s = jnp.einsum("shk,thk->hst", q, k,
                       precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
        causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hst,thk->shk", p, v,
                       precision=jax.lax.Precision.HIGHEST)
        h = h + mm(o.reshape(S, H * hd), g("attn/wo").reshape(H * hd, d),
                   control)
        x = rmsnorm(h, g("ln2/scale"), EPS)
        a = mm(x, g("ffn/wi"), control)
        b = mm(x, g("ffn/wg"), control)
        return h + mm(a * jax.nn.silu(b), g("ffn/wo"), control)

    return jax.jit(layer, static_argnums=2)


def logits(cfg: dict, seed: int, samples, control: bool = False):
    """Per sample, the reference's logits at every served token's
    position (and the control's, or None)."""
    top, layer_spec = specs(cfg)
    walk = Walk(seed, rule, top, layer_spec)
    seqs = sequences(samples)
    d = cfg["d_model"]
    table = walk.leaf("embed/table")
    hs = []
    for seq, _, _ in seqs:
        padded = np.zeros(bucket(len(seq)), np.int32)
        padded[:len(seq)] = seq
        hs.append(table[jnp.asarray(padded)] * math.sqrt(d))
    del table
    hc = list(hs) if control else None
    layer = make_layer(cfg)
    for li in range(cfg["n_layers"]):
        w = walk.layer(li)
        hs = [layer(w, h, False) for h in hs]
        if control:
            hc = [layer(w, h, True) for h in hc]
    norm = walk.leaf("final_norm/scale")
    head = walk.leaf("lm_head/w")
    out = []
    for i, (seq, first, served) in enumerate(seqs):
        sl = slice(first, first + len(served))
        ref = mm(rmsnorm(hs[i][sl], norm, EPS), head, False)
        ctl = (mm(rmsnorm(hc[i][sl], norm, EPS), head, True)
               if control else None)
        out.append((ref, ctl))
    return out
