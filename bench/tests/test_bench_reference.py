"""The plain references against the program's prefill-then-decode logits
at a small size, the weights they share, and the control that the check
must fail."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.reference import common, llama, rwkv6

SEED = 2**40 + 12345
LLAMA = dict(name="t", family="dense", n_layers=2, d_model=64, n_heads=4,
             n_kv_heads=2, d_ff=128, vocab=256, head_dim=16,
             rope_theta=5e6)
RWKV = dict(name="t", family="ssm", n_layers=2, d_model=128, n_heads=0,
            n_kv_heads=0, d_ff=256, vocab=256, head_dim=64,
            block_pattern=("rwkv",))
FAMILIES = [("llama", llama, LLAMA), ("rwkv6", rwkv6, RWKV)]


def program(kw, dtype):
    from repro.models.config import ModelConfig
    return ModelConfig(**kw, param_dtype=dtype, compute_dtype=dtype)


def served(cfg, params, prompt, n_new):
    """The program's own path: a paged prefill, then greedy decode steps
    through the page pool.  Returns the tokens and each one's logits."""
    from repro.serving import PagedJaxModelBackend
    pb = PagedJaxModelBackend(cfg, params, 128, page_size=16)
    shard, _ = pb.init(2)
    logits, st = pb._prefill(params, {"tokens": jnp.asarray(prompt[None])})
    shard = pb.splice(shard, [(0, pb._fresh_handle(st, 0, len(prompt)))])
    out, rows = [], []
    row = np.asarray(logits[0], np.float32)
    for _ in range(n_new):
        rows.append(row)
        out.append(int(np.argmax(row)))
        pb._ensure_pages(shard)
        tok = jnp.asarray([[out[-1]], [0]], jnp.int32)
        lg, shard.states = pb._decode(params, tok, shard.states,
                                      jnp.asarray(shard.table),
                                      jnp.asarray(shard.lengths))
        shard.lengths = shard.lengths + 1
        row = np.asarray(lg[0], np.float32)
    return np.array(out, np.int32), np.stack(rows)


@pytest.mark.parametrize("name,mod,kw", FAMILIES, ids=[f[0] for f in FAMILIES])
def test_reference_matches_prefill_then_decode(name, mod, kw):
    cfg = program(kw, "float32")
    from repro.models import api
    params = weights.make_params(api.params_specs(cfg), SEED, mod.rule)
    prompt = np.random.default_rng(0).integers(1, 256, 40).astype(np.int32)
    toks, got = served(cfg, params, prompt, 12)
    with jax.default_matmul_precision("highest"):
        (want, _), = mod.logits(dict(kw, param_dtype="float32"), SEED,
                                [(prompt, toks)])
    want = np.asarray(want)
    assert want.shape == got.shape
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 2e-4 * scale
    assert (np.argmax(want, -1) == toks).all()


@pytest.mark.parametrize("name,mod,kw", FAMILIES, ids=[f[0] for f in FAMILIES])
def test_control_fails_where_bfloat16_passes(name, mod, kw):
    """At a small size: the program in bfloat16 reads a gap well under
    the control's, which is float8."""
    cfg = program(kw, "bfloat16")
    from repro.models import api
    params = weights.make_params(api.params_specs(cfg), SEED, mod.rule)
    rng = np.random.default_rng(1)
    samples = []
    for n in (24, 40, 56):
        prompt = rng.integers(1, 256, n).astype(np.int32)
        samples.append((prompt, served(cfg, params, prompt, 16)[0]))
    got = common.served_gaps(mod, dict(kw, param_dtype="bfloat16"), SEED,
                             samples, control=True)
    gap = max(float(g.max()) for g, _ in got)
    ctl = max(float(c.max()) for _, c in got)
    assert ctl >= 3 * gap, (gap, ctl)


def test_a_stacked_layer_draws_as_the_reference_rebuilds_it():
    spec = {"stage0": {"w": jax.ShapeDtypeStruct((3, 8, 5), jnp.bfloat16)},
            "top": jax.ShapeDtypeStruct((4,), jnp.float32)}
    rule = lambda p, s: ("std", 0.5)
    tree = weights.make_params(spec, SEED, rule)
    base = weights.base_key(SEED)
    for layer in range(3):
        one = weights.layer_leaf(base, "stage0/w", layer, (8, 5),
                                 jnp.bfloat16, rule("", ()))
        assert (np.asarray(tree["stage0"]["w"][layer], np.float32)
                == np.asarray(one, np.float32)).all()
    assert not np.allclose(np.asarray(tree["stage0"]["w"][0], np.float32),
                           np.asarray(tree["stage0"]["w"][1], np.float32))


def test_seeds_past_32_bits_differ():
    a = weights.base_key(5)
    b = weights.base_key(5 + 2**32)
    assert not np.array_equal(np.asarray(jax.random.key_data(a)),
                              np.asarray(jax.random.key_data(b)))


def test_fake_float8_rounds_to_three_mantissa_bits():
    x = jnp.asarray([[1.0, 1.0 + 1 / 16, 1.0 + 1 / 8, 448.0]])
    y = np.asarray(common.fake_f8(x, -1))
    assert y[0, 1] in (1.0, 1.125) and y[0, 2] == 1.125 and y[0, 3] == 448.0


CONFIGS = Path(__file__).resolve().parents[1] / "configs"


@pytest.mark.parametrize("path", sorted(CONFIGS.glob("*.json")),
                         ids=lambda p: p.name)
def test_config_file_is_the_published_model(path):
    """The program block holds the published sizes unchanged."""
    c = json.loads(path.read_text())
    pub, prog = c["published"], c["program"]
    assert c["reduced"] == []
    assert prog["d_model"] == pub["hidden_size"]
    assert prog["n_layers"] == pub["num_hidden_layers"]
    assert prog["vocab"] == pub["vocab_size"]
    assert prog["d_ff"] == pub["intermediate_size"]
    if "num_attention_heads" in pub:
        assert prog["n_heads"] == pub["num_attention_heads"]
        assert prog["n_kv_heads"] == pub["num_key_value_heads"]
        assert prog["rope_theta"] == pub["rope_theta"]
    if "head_size" in pub:
        assert prog["head_dim"] == pub["head_size"]
    from repro.configs import get_config
    zoo = get_config(prog["name"])
    for k in ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
              "vocab"):
        assert getattr(zoo, k) == prog[k], k
