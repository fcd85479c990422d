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

# The cuts the model-configs guide allows a configuration (section 4):
# the depth, the experts held here, the vocabulary.  Every other key is a
# width or a count the chip's share leaves as published.
CUTS = ("n_layers", "n_experts", "vocab")
# program key -> the published key it must equal; a file's
# ``published_as`` adds to and overrides these
PUBLISHED_AS = {"d_model": "hidden_size", "n_layers": "num_hidden_layers",
                "vocab": "vocab_size", "d_ff": "intermediate_size",
                "n_heads": "num_attention_heads",
                "n_kv_heads": "num_key_value_heads",
                "head_dim": "head_size", "rope_theta": "rope_theta"}
# compared whatever the published config holds; the rest where it has them
ALWAYS = ("d_model", "n_layers", "vocab", "d_ff")
# program keys that name no size of the source
PROGRAM_ONLY = ("name", "family", "block_pattern", "param_dtype",
                "compute_dtype")


def is_width(key: str) -> bool:
    """A width, in the contract's sense: a hidden, intermediate, state or
    projection size, a head count or size, experts per token."""
    return (key in ("d_model", "d_ff", "n_heads", "n_kv_heads", "head_dim",
                    "top_k", "lru_width", "conv_width", "window")
            or key.startswith("d_")
            or key.endswith(("_dim", "_rank", "_width", "_size")))
ZOO_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff", "vocab")


def check_config(c: dict) -> None:
    """A configuration file holds the published model: each program key
    equals the published key it maps to, but the cuts listed in
    ``reduced``, each of which ``cut`` gives with its published value,
    keeps to its floor, and ``deployment`` accounts for it.  A program
    key that maps to no published key is the program's own
    (``PROGRAM_ONLY``) or is named in ``departs``, and is no width."""
    pub, prog = c["published"], c["program"]
    reduced, cut = c["reduced"], c.get("cut", {})
    bad = [k for k in reduced if k not in CUTS]
    assert not bad, f"reduced lists {bad}: only {CUTS} may be cut"
    assert sorted(cut) == sorted(reduced), \
        f"cut {sorted(cut)} is not reduced {sorted(reduced)}"
    mapped = c.get("published_as", {})
    published_as = {**PUBLISHED_AS, **mapped}
    for k in reduced:
        assert published_as.get(k) in pub, \
            f"cut {k}: no published key to hold it to (published_as)"
    # every program key is held to the source, or says why not
    departs = c.get("departs", {})
    for k in prog:
        if k in PROGRAM_ONLY or k in published_as:
            continue
        assert k in departs, \
            f"{k}: maps to no published key (published_as) and is not " \
            f"in departs"
        assert not is_width(k), \
            f"{k}: a width departs from the source; a width is never cut"
    for k, pk in published_as.items():
        if pk not in pub:
            assert k not in mapped and k not in ALWAYS, \
                f"the published config has no {pk!r} for {k!r}"
            # a width the source does not state is one the model lacks
            assert not (is_width(k) and prog.get(k)), \
                f"{k} {prog.get(k)}: the published config states no " \
                f"{pk!r}; map {k!r} to the key that holds it"
            continue
        assert k in prog, f"the program block has no {k!r} ({pk!r})"
        if k in reduced:
            assert cut[k] == pub[pk], f"cut {k}: {cut[k]} is not {pk} " \
                                      f"{pub[pk]}"
            assert prog[k] < pub[pk], f"cut {k}: {prog[k]} cuts nothing"
        else:
            assert prog[k] == pub[pk], f"{k} {prog[k]} != {pk} {pub[pk]}"
    if reduced:
        dep = c["deployment"]
        chips = dep["chips_per_layer"]
        assert isinstance(chips, int) and chips >= 1 and dep["how"]
    if "n_layers" in reduced:
        lead = prog.get("first_k_dense", 0)
        period = len(prog.get("block_pattern", ["attn"]))
        assert prog["n_layers"] - lead >= max(period, 4), \
            f"n_layers {prog['n_layers']}: under a whole period of " \
            f"{period} and four layers after {lead} leading dense ones"
    if "n_experts" in reduced:
        assert prog["n_experts"] >= 8, \
            f"n_experts {prog['n_experts']}: under 8 experts held"
        assert prog["n_experts"] * chips >= cut["n_experts"], \
            f"{chips} chips of {prog['n_experts']} experts do not hold " \
            f"the {cut['n_experts']} published"
    if "vocab" in reduced:
        assert 8 * prog["vocab"] >= cut["vocab"], \
            f"vocab {prog['vocab']}: under an eighth of {cut['vocab']}"
        assert prog["vocab"] * chips >= cut["vocab"], \
            f"{chips} chips of {prog['vocab']} rows do not hold the " \
            f"{cut['vocab']} published"
    from repro.configs import get_config
    zoo = get_config(prog["name"])
    for k in ZOO_KEYS:
        if k not in reduced:
            assert getattr(zoo, k) == prog[k], k


@pytest.mark.parametrize("path", sorted(CONFIGS.glob("*.json")),
                         ids=lambda p: p.name)
def test_config_file_is_the_published_model(path):
    """The program block holds the published sizes, but the cuts that
    the file lists and accounts for."""
    check_config(json.loads(path.read_text()))


def yi():
    return json.loads((CONFIGS / "yi-6b.json").read_text())


def yi_cut(n_layers=8):
    c = yi()
    c["program"]["n_layers"] = n_layers
    c.update(reduced=["n_layers"], cut={"n_layers": 32},
             deployment={"chips_per_layer": 1,
                         "how": "one stage of a pipeline of 32 / n layers"})
    return c


def deepseek_moe(held=8, chips=8, **prog):
    """DeepSeekMoE-16B (deepseek-ai/deepseek-moe-16b-base, arXiv:2401.06066)
    held at 8 of its 64 routed experts a layer, as eight chips share
    them."""
    pub = {"hidden_size": 2048, "intermediate_size": 10944,
           "moe_intermediate_size": 1408, "n_routed_experts": 64,
           "n_shared_experts": 2, "num_experts_per_tok": 6,
           "first_k_dense_replace": 1, "num_attention_heads": 16,
           "num_key_value_heads": 16, "num_hidden_layers": 28,
           "vocab_size": 102400, "rope_theta": 10000.0}
    program = {"name": "deepseek-moe-16b", "family": "moe", "n_layers": 28,
               "d_model": 2048, "n_heads": 16, "n_kv_heads": 16,
               "d_ff": 1408, "d_ff_dense": 10944, "vocab": 102400,
               "n_experts": held, "top_k": 6, "n_shared_experts": 2,
               "first_k_dense": 1, "rope_theta": 10000.0, **prog}
    return {"published": pub, "program": program,
            "reduced": ["n_experts"], "cut": {"n_experts": 64},
            "published_as": {"d_ff": "moe_intermediate_size",
                             "d_ff_dense": "intermediate_size",
                             "n_experts": "n_routed_experts",
                             "top_k": "num_experts_per_tok",
                             "n_shared_experts": "n_shared_experts",
                             "first_k_dense": "first_k_dense_replace"},
            "deployment": {"chips_per_layer": chips,
                           "how": "expert parallel: 8 of the 64 routed "
                                  "experts of every layer on each chip"}}


def _set(c, where, key, value):
    c[where][key] = value
    return c


def _drop(c, where, key):
    del c[where][key]
    return c


def _vocab_cut(vocab):
    c = yi()
    c["program"]["vocab"] = vocab
    c.update(reduced=["vocab"], cut={"vocab": 64000},
             deployment={"chips_per_layer": 8,
                         "how": "the vocabulary sliced over eight chips"})
    return c


PLANTED = {
    # passes
    "depth_cut_listed": (yi_cut, None),
    "published_without_rope_theta": (
        lambda: _drop(yi(), "published", "rope_theta"), None),
    "experts_held_with_mapped_widths": (deepseek_moe, None),
    "vocabulary_an_eighth": (lambda: _vocab_cut(8000), None),
    # fails, for the reason given
    "depth_cut_unlisted": (
        lambda: dict(yi_cut(), reduced=[], cut={}), "n_layers 8 !="),
    "listed_but_not_given": (lambda: dict(yi_cut(), cut={}),
                             "is not reduced"),
    "width_listed": (lambda: dict(
        _set(yi(), "program", "d_ff", 5504), reduced=["d_ff"],
        cut={"d_ff": 11008}), "only .* may be cut"),
    "depth_under_floor": (lambda: yi_cut(3), "under a whole period"),
    "experts_under_floor": (lambda: deepseek_moe(held=4),
                            "under 8 experts"),
    "experts_not_held_by_the_chips": (lambda: deepseek_moe(chips=4),
                                      "do not hold"),
    "experts_per_token_changed": (lambda: deepseek_moe(top_k=2),
                                  "top_k 2 != num_experts_per_tok"),
    "experts_cut_with_no_published_key": (lambda: _drop(
        deepseek_moe(), "published_as", "n_experts"),
        "no published key to hold it to"),
    "vocabulary_under_an_eighth": (lambda: _vocab_cut(7000),
                                   "under an eighth"),
    "published_as_mismatch": (lambda: dict(
        yi(), published_as={"d_ff": "max_position_embeddings"}),
        "d_ff 11008 != max_position_embeddings"),
    "width_unmapped": (lambda: _drop(
        deepseek_moe(top_k=2), "published_as", "top_k"),
        "top_k: maps to no published key"),
    "width_excused_by_departs": (lambda: dict(
        _drop(deepseek_moe(top_k=2), "published_as", "top_k"),
        departs={"top_k": "two experts a token"}),
        "top_k: a width departs"),
    "program_key_unaccounted": (lambda: _set(
        yi(), "program", "logits_softcap", 30.0),
        "logits_softcap: maps to no published key"),
    "program_key_in_departs": (lambda: dict(
        _set(yi(), "program", "logits_softcap", 30.0),
        departs={"logits_softcap": "the program caps its logits at 30"}),
        None),
    "width_its_source_does_not_state": (lambda: _drop(
        deepseek_moe(n_kv_heads=4), "published", "num_key_value_heads"),
        "n_kv_heads 4: the published config states no"),
    "published_as_missing_key": (lambda: dict(
        yi(), published_as={"d_ff": "moe_intermediate_size"}),
        "has no 'moe_intermediate_size'"),
}


@pytest.mark.parametrize("case", sorted(PLANTED))
def test_config_contract_on_planted_files(case):
    make, refused = PLANTED[case]
    if refused is None:
        check_config(make())
    else:
        with pytest.raises(AssertionError, match=refused):
            check_config(make())
