"""What the plain references share: float32 at ``highest`` precision,
the layer-by-layer walk, the lower-precision control, and the gap that
decides ``correct``.

A reference is given the configuration (the file's ``program`` block),
the seed and the served requests; it imports nothing of the program and
takes nothing the program made.  It rebuilds each layer's weights from
the seed (``bench.weights``), one layer at a time, so a model whose
float32 copy would not fit the chip still fits.

The number compared is the **gap**: for every served token, the
reference's best logit at that position less the reference's logit of
the served token.  Greedy decoding in the program's precision picks a
token whose reference logit lies a little below the best where two are
nearly tied; a fault, or a lower precision, picks tokens further down.
The control is the same reference computed with every weight matmul's
operands rounded to float8 (e4m3, scaled per row and per column): the
precision below the configuration's bfloat16.  Its gap is read for the
token the control itself puts first.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

F8_MAX = 448.0          # largest finite float8_e4m3fn


def fake_f8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along
    ``axis``, and back to float32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x, w, control: bool):
    """``x (..., n) @ w (n, m)`` in float32, or in float8 for the
    control."""
    if control:
        x = fake_f8(x, -1)
        w = fake_f8(w, 0)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def bucket(n: int, step: int = 512) -> int:
    return int(math.ceil(n / step) * step)


class Walk:
    """Rebuilds the weights of a layered model from the seed, one layer
    at a time, in float32 (the served values, widened)."""

    def __init__(self, seed: int, rule, top: dict, layer: dict):
        """``top``: path -> (shape, dtype) of unstacked leaves; ``layer``:
        path -> (shape, dtype) of one layer of each stacked leaf."""
        self.base = weights.base_key(seed)
        self.rule = rule
        self.top = top
        self.layer_spec = layer

        def one_layer(base, l):
            return {p: weights.layer_leaf(base, p, l, s, dt, rule(p, s))
                    .astype(jnp.float32)
                    for p, (s, dt) in layer.items()}

        self._layer = jax.jit(one_layer)

    def leaf(self, path: str):
        s, dt = self.top[path]
        k = weights.leaf_key(self.base, path)
        return jax.jit(lambda k: weights.draw(k, s, dt, self.rule(path, s))
                       .astype(jnp.float32))(k)

    def layer(self, l: int) -> dict:
        return self._layer(self.base, jnp.uint32(l))


def sequences(samples):
    """What the reference reads: each prompt followed by its served
    tokens but the last, and where each served token is predicted."""
    out = []
    for prompt, served in samples:
        prompt = np.asarray(prompt, np.int32)
        served = np.asarray(served, np.int32)
        seq = np.concatenate([prompt, served[:-1]])
        out.append((seq, len(prompt) - 1, served))
    return out


@jax.jit
def _gaps(ref_logits, served, ctl_logits):
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, served[:, None], axis=-1)[:, 0]
    pick = jnp.argmax(ctl_logits, axis=-1)
    ctl = jnp.take_along_axis(ref_logits, pick[:, None], axis=-1)[:, 0]
    return best - got, best - ctl


def served_gaps(module, cfg: dict, seed: int, samples,
                control: bool = False) -> list:
    """Per sample: the gap of every served token, and the control's
    (None without the control).  ``module`` is a family's reference."""
    out = []
    for (_, served), (ref, ctl) in zip(
            samples, module.logits(cfg, seed, samples, control)):
        g, gc = _gaps(ref, jnp.asarray(np.asarray(served, np.int32)),
                      ref if ctl is None else ctl)
        out.append((np.asarray(g), None if ctl is None else np.asarray(gc)))
    return out
