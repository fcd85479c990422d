"""Plain float32 forward of an RWKV-6 (Finch) stack, as the program
serves it.

Per layer: a time mix (token shift, the r/k/v/g projections, the decay
``w = exp(-exp(w0 + tanh(x A) B))``, the wkv recurrence with its bonus
``u``, a per-head group norm and the output projection) and a channel
mix (token shift, squared-ReLU key, value, sigmoid receptance).  Where
the program's variant departs from the published Finch, this follows the
program, since the weights are the program's: RMSNorm (``1 + scale``,
epsilon 1e-6) in place of LayerNorm; static token-shift mixes, where
Finch interpolates them with a data-dependent low-rank term; the
embedding scaled by sqrt(d_model).  No cache and no kernels: the
recurrence is a plain scan over time, every sequence from a zero state.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.common import Walk, bucket, mm, rmsnorm, sequences

EPS = 1e-6
GN_EPS = 1e-5
LORA = 64
STAGE = "stage0/b0_rwkv/"


def rule(path: str, shape):
    name = path.rsplit("/", 1)[-1]
    if name in ("scale", "ln_x"):
        return ("std", 0.1)
    if name == "mix":
        return ("range", 0.0, 1.0)
    if name == "w0":
        return ("range", -6.0, -1.0)
    if name == "u":
        return ("std", 0.5)
    if path == "embed/table":
        return ("std", 1.0 / math.sqrt(shape[1]))
    return ("std", 1.0 / math.sqrt(shape[0]))


def specs(cfg: dict):
    d, f, V = cfg["d_model"], cfg["d_ff"], cfg["vocab"]
    hd = cfg.get("head_dim") or 64
    H = d // hd
    bf = jnp.dtype(cfg.get("param_dtype", "bfloat16"))
    f32 = jnp.float32
    top = {"embed/table": ((V, d), bf), "final_norm/scale": ((d,), bf),
           "lm_head/w": ((d, V), bf)}
    t, c = STAGE + "tmix/", STAGE + "cmix/"
    layer = {STAGE + "ln1/scale": ((d,), bf), STAGE + "ln2/scale": ((d,), bf),
             t + "mix": ((5, d), bf), t + "wr": ((d, d), bf),
             t + "wk": ((d, d), bf), t + "wv": ((d, d), bf),
             t + "wg": ((d, d), bf), t + "w0": ((d,), f32),
             t + "w_lora_a": ((d, LORA), bf), t + "w_lora_b": ((LORA, d), bf),
             t + "u": ((H, hd), f32), t + "wo": ((d, d), bf),
             t + "ln_x": ((d,), f32),
             c + "mix": ((2, d), bf), c + "wk": ((d, f), bf),
             c + "wv": ((f, d), bf), c + "wr": ((d, d), bf)}
    return top, layer


def shift(x):
    """(B, S, D): each position sees the one before it, the first zeros."""
    return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]


def make_layer(cfg: dict):
    d = cfg["d_model"]
    hd = cfg.get("head_dim") or 64
    H = d // hd

    def layer(w, h, control):
        t = lambda n: w[STAGE + "tmix/" + n]
        c = lambda n: w[STAGE + "cmix/" + n]
        B, S, _ = h.shape
        x = rmsnorm(h, w[STAGE + "ln1/scale"], EPS)
        xp = shift(x)
        mix = t("mix")
        m = [x + (xp - x) * mix[i] for i in range(5)]
        r = mm(m[0], t("wr"), control).reshape(B, S, H, hd)
        k = mm(m[1], t("wk"), control).reshape(B, S, H, hd)
        v = mm(m[2], t("wv"), control).reshape(B, S, H, hd)
        g = mm(m[3], t("wg"), control)
        lora = mm(jnp.tanh(mm(m[4], t("w_lora_a"), control)),
                  t("w_lora_b"), control)
        dec = jnp.exp(-jnp.exp(t("w0") + lora)).reshape(B, S, H, hd)
        u = t("u")

        def step(state, inp):
            r_, k_, v_, w_ = inp                       # (B, H, hd)
            kv = k_[..., :, None] * v_[..., None, :]
            o = jnp.einsum("bhk,bhkv->bhv", r_, state + u[None, :, :, None]
                           * kv, precision=jax.lax.Precision.HIGHEST)
            return w_[..., :, None] * state + kv, o

        s0 = jnp.zeros((B, H, hd, hd), jnp.float32)
        _, o = jax.lax.scan(step, s0, tuple(a.swapaxes(0, 1)
                                            for a in (r, k, v, dec)))
        o = o.swapaxes(0, 1)                           # (B, S, H, hd)
        mu = o.mean(-1, keepdims=True)
        var = o.var(-1, keepdims=True)
        o = ((o - mu) * jax.lax.rsqrt(var + GN_EPS)).reshape(B, S, d)
        o = o * (1.0 + t("ln_x")) * jax.nn.silu(g)
        h = h + mm(o, t("wo"), control)
        y = rmsnorm(h, w[STAGE + "ln2/scale"], EPS)
        yp = shift(y)
        cm = c("mix")
        xk = y + (yp - y) * cm[0]
        xr = y + (yp - y) * cm[1]
        kk = jnp.square(jax.nn.relu(mm(xk, c("wk"), control)))
        return h + jax.nn.sigmoid(mm(xr, c("wr"), control)) \
            * mm(kk, c("wv"), control)

    return jax.jit(layer, static_argnums=2)


def logits(cfg: dict, seed: int, samples, control: bool = False):
    """Per sample, the reference's logits at every served token's
    position (and the control's, or None)."""
    top, layer_spec = specs(cfg)
    walk = Walk(seed, rule, top, layer_spec)
    seqs = sequences(samples)
    d = cfg["d_model"]
    L = bucket(max(len(s) for s, _, _ in seqs))
    tokens = np.zeros((len(seqs), L), np.int32)
    for i, (seq, _, _) in enumerate(seqs):
        tokens[i, :len(seq)] = seq
    table = walk.leaf("embed/table")
    h = table[jnp.asarray(tokens)] * math.sqrt(d)
    del table
    hc = h if control else None
    layer = make_layer(cfg)
    for li in range(cfg["n_layers"]):
        w = walk.layer(li)
        h = layer(w, h, False)
        if control:
            hc = layer(w, hc, True)
    norm = walk.leaf("final_norm/scale")
    head = walk.leaf("lm_head/w")
    out = []
    for i, (seq, first, served) in enumerate(seqs):
        sl = slice(first, first + len(served))
        ref = mm(rmsnorm(h[i, sl], norm, EPS), head, False)
        ctl = (mm(rmsnorm(hc[i, sl], norm, EPS), head, True)
               if control else None)
        out.append((ref, ctl))
    return out
