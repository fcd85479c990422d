"""Model zoo public API: step functions, input specs, bubble trees.

``input_specs`` returns ShapeDtypeStruct stand-ins (weak-type-correct,
shardable, no device allocation) for every model input of a given workload
shape — the currency of the multi-pod dry-run.

``bubble_tree`` emits the planner-side bubble tree for an (arch × shape)
cell: the application-structure description the bubble scheduler consumes
to derive the sharding plan.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.bubble import Bubble, bubble
from repro.core.planner import Dim

from . import lm
from .config import ModelConfig
from .schema import init_params, param_dims, param_shapes


# ---------------------------------------------------------------------------
# workload shapes (the assigned input-shape set)
# ---------------------------------------------------------------------------

SHAPES = {
    "train_4k":    dict(kind="train",   seq=4_096,   batch=256),
    "prefill_32k": dict(kind="prefill", seq=32_768,  batch=32),
    "decode_32k":  dict(kind="decode",  seq=32_768,  batch=128),
    "long_500k":   dict(kind="decode",  seq=524_288, batch=1),
}


def shape_applicable(cfg: ModelConfig, shape: str) -> tuple[bool, str]:
    """Whether an (arch × shape) cell is lowered (DESIGN §Arch-applicability)."""
    info = SHAPES[shape]
    if shape == "long_500k" and not cfg.sub_quadratic:
        return False, "full-attention arch: 500k dense KV cache is skipped"
    if info["kind"] == "decode" and cfg.enc_layers and shape == "long_500k":
        return False, "enc-dec decoder is full-attention"
    return True, ""


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct, no allocation)
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def train_specs(cfg: ModelConfig, batch: int, seq: int) -> dict:
    specs = {}
    if cfg.enc_layers or cfg.frontend == "audio":
        # enc-dec: source = stub frames, target = tokens
        specs["frontend_embeds"] = _sds((batch, seq, cfg.d_model), "bfloat16")
        specs["tokens"] = _sds((batch, seq), "int32")
        specs["labels"] = _sds((batch, seq), "int32")
    elif cfg.frontend == "vision":
        P = min(cfg.frontend_tokens, seq - 16)
        specs["frontend_embeds"] = _sds((batch, P, cfg.d_model), "bfloat16")
        specs["tokens"] = _sds((batch, seq - P), "int32")
        specs["labels"] = _sds((batch, seq - P), "int32")
    else:
        specs["tokens"] = _sds((batch, seq), "int32")
        specs["labels"] = _sds((batch, seq), "int32")
    return specs


def prefill_specs(cfg: ModelConfig, batch: int, seq: int) -> dict:
    specs = train_specs(cfg, batch, seq)
    specs.pop("labels", None)
    return specs


def decode_specs(cfg: ModelConfig, batch: int, seq: int) -> dict:
    """One decode step against a cache of logical length ``seq``."""
    c = lm._dec_cfg(cfg) if cfg.enc_layers else cfg
    states = jax.eval_shape(
        lambda: lm.init_state(c, batch, seq, start_pos=seq))
    specs = {"token": _sds((batch, 1), "int32"), "states": states}
    if cfg.enc_layers:
        specs["enc"] = _sds((batch, min(seq, 4096), cfg.d_model), "bfloat16")
    return specs


def params_specs(cfg: ModelConfig):
    return param_shapes(lm.lm_schema(cfg))


def input_specs(cfg: ModelConfig, shape: str) -> dict:
    info = SHAPES[shape]
    fn = {"train": train_specs, "prefill": prefill_specs,
          "decode": decode_specs}[info["kind"]]
    return fn(cfg, info["batch"], info["seq"])


# ---------------------------------------------------------------------------
# step functions
# ---------------------------------------------------------------------------

def make_loss_fn(cfg: ModelConfig, use_kernel: bool = False,
                 remat: bool = False):
    def loss(params, batch):
        return lm.loss_fn(params, batch, cfg, use_kernel=use_kernel,
                          remat=remat)
    return loss


# The step functions carry stable names: a jitted one's program is
# ``jit_<name>`` in a profiler trace (``jit_paged_decode`` for the served
# decode), which is how the trace's readers find its device time.

def make_prefill_fn(cfg: ModelConfig, cache_len: int,
                    use_kernel: bool = False):
    if cfg.enc_layers:
        def prefill(params, batch):
            return lm.encdec_prefill(params, batch, cfg, cache_len)
        return prefill
    def prefill(params, batch):
        return lm.prefill(params, batch, cfg, cache_len,
                          use_kernel=use_kernel)
    return prefill


def make_decode_fn(cfg: ModelConfig):
    if cfg.enc_layers:
        def decode(params, token, states, enc):
            return lm.encdec_decode_step(params, token, states, enc, cfg)
        return decode
    def decode(params, token, states):
        return lm.decode_step(params, token, states, cfg)
    return decode


def make_paged_decode_fn(cfg: ModelConfig, use_kernel: bool = False):
    """Decode step over paged KV: ``(params, token, states, tables,
    lengths) -> (logits, states)``.  See ``models.paged``."""
    from . import paged

    def paged_decode(params, token, states, tables, lengths):
        return paged.decode_step(params, token, states, tables, lengths,
                                 cfg, use_kernel=use_kernel)
    return paged_decode


def batch_axis_spec(init_fn):
    """Infer, per state leaf, which axis carries the batch.

    ``init_fn(batch)`` builds (or ``eval_shape``s) a state pytree for a
    given batch size.  Comparing the leaf shapes at two batch sizes pins
    the batch axis exactly: the one axis whose extent differs.  Returns a
    matching pytree of ints — the batch axis, or ``-1`` for batch-free
    leaves (shared pools, scalars), which splice/extract must pass
    through untouched.

    This replaces the ``ndim >= 2`` heuristic the serving backends used
    to guess batch leaves with: that guess silently skipped genuine 1-D
    per-slot leaves (a ``(B,)`` position or flag vector) and corrupted
    nothing only as long as no model had one.  An explicit spec fails
    loudly instead: a leaf whose shape varies on more than one axis is a
    structural error, not a leaf to skip.
    """
    a = jax.eval_shape(lambda: init_fn(2))
    b = jax.eval_shape(lambda: init_fn(3))

    def one(x, y):
        assert len(x.shape) == len(y.shape), (x.shape, y.shape)
        diff = [i for i, (p, q) in enumerate(zip(x.shape, y.shape))
                if p != q]
        if not diff:
            return -1
        if len(diff) > 1:
            raise ValueError(
                f"state leaf varies on {len(diff)} axes with batch "
                f"({x.shape} vs {y.shape}): not a batch-sliceable leaf")
        return diff[0]

    return jax.tree.map(one, a, b)


def init(cfg: ModelConfig, key: jax.Array, shardings=None):
    """Random parameters for ``cfg``, drawn in one jitted program.

    Under jit each leaf is drawn in float32 and cast in one fused pass, so
    no float32 copy of a leaf is ever materialised: op by op, the last
    ``(32, 4096, 11008)`` leaf of yi-6b needs a 5.8 GB float32 temporary
    on top of the ~12 GB already placed.  ``shardings`` (a tree of
    shardings matching the parameters) places every leaf where it is
    made, so no device ever holds the whole model."""
    return jax.jit(functools.partial(init_params, lm.lm_schema(cfg)),
                   out_shardings=shardings)(key)


def dims(cfg: ModelConfig):
    return param_dims(lm.lm_schema(cfg))


# ---------------------------------------------------------------------------
# bubble tree for the placement planner
# ---------------------------------------------------------------------------

def bubble_tree(cfg: ModelConfig, shape: str) -> Bubble:
    """The application-structure description for one (arch × shape) cell.

    Nesting: train_step ⊃ {data bubble, layer bubble ⊃ {attn, ffn/moe,
    rec/rwkv sub-bubbles}, embed bubble}.  Parameter dims set
    ``min_level="model"`` so their collectives stay on the innermost
    (cheapest) axis — the affinity statement; the data bubble tolerates any
    level (batch gradients all-reduce across pods by design).
    """
    info = SHAPES[shape]
    root = bubble(name=f"{cfg.name}:{shape}")
    root.insert(bubble(Dim(name="batch", width=info["batch"], weight=1.0,
                           is_activation=True),
                       name="data"))

    layer = bubble(name="layer", burst_level="model")
    kinds = set(cfg.block_pattern)
    if "attn" in kinds or cfg.enc_layers:
        layer.insert(bubble(
            Dim(name="heads", width=max(cfg.n_heads, 1), weight=2.5),
            Dim(name="kv_heads", width=max(cfg.n_kv_heads, 1), weight=1.0),
            name="attn"))
    if "rec" in kinds:
        layer.insert(bubble(
            Dim(name="lru", width=cfg.lru_width or cfg.d_model, weight=2.5),
            name="rec"))
    if "rwkv" in kinds:
        layer.insert(bubble(
            Dim(name="heads_flat", width=cfg.d_model, weight=2.5),
            name="tmix"))
    if cfg.n_experts:
        layer.insert(bubble(
            Dim(name="experts", width=cfg.n_experts, weight=4.0),
            Dim(name="d_ff", width=cfg.d_ff, weight=2.0),
            name="moe"))
        # NOTE: a separate shared-expert bubble (d_ff_shared -> model) was
        # tried and REFUTED: TP partial-sum all-reduces of the shared FFN
        # outweigh its compute saving (EXPERIMENTS.md §Perf, deepseek iter 2)

    else:
        layer.insert(bubble(
            Dim(name="d_ff", width=cfg.d_ff, weight=2.0),
            name="ffn"))
    root.insert(layer)
    root.insert(bubble(
        Dim(name="vocab", width=cfg.vocab, weight=1.5, min_level="model"),
        name="embed"))
    return root
