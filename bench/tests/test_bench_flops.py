"""``bench/flops.py`` against counts made by hand for one layer."""

import json
from pathlib import Path

import pytest

from bench import flops

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def program(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["program"]


def one_layer(cfg):
    return dict(cfg, n_layers=1)


def test_yi_layer_by_hand():
    cfg = program("yi-6b")
    # q 4096x32x128, k and v 4096x4x128, o 32x128x4096, mlp 3x4096x11008
    weights = (4096 * 4096 + 2 * 4096 * 512 + 4096 * 4096
               + 3 * 4096 * 11008)
    assert flops.layer_matmul_weights(cfg, "attn") == weights
    head = 4096 * 64000
    # one token after 1000 others: 2 per weight, 4 x 32 heads x 128 per
    # position attended (1001 positions)
    got = flops.decode_flops(one_layer(cfg), [1000])
    assert got == 2 * (weights + head) + 4 * 32 * 128 * 1001
    # bytes: bf16 weights and norms, the head and final norm, one
    # embedding row, and the KV of 1000 positions read plus one written
    kv = 2 * 4 * 128 * 2
    want = (2 * weights + 2 * 4096 * 2 + 2 * head + 2 * 4096
            + 2 * 4096 + kv * 1000 + kv)
    assert flops.decode_bytes(one_layer(cfg), [1000]) == want


def test_yi_prefill_is_causal():
    cfg = one_layer(program("yi-6b"))
    w = flops.layer_matmul_weights(cfg, "attn")
    S = 256
    want = 2 * w * S + 4 * 32 * 128 * S * (S + 1) // 2 + 2 * 4096 * 64000
    assert flops.prefill_flops(cfg, S) == want


def test_rwkv_layer_by_hand():
    cfg = program("rwkv6-3b")
    d, f = 2560, 8960
    # r k v g o (5 d x d), the decay's 2 x d x 64, channel mix k, v, r
    weights = 5 * d * d + 2 * d * 64 + d * f + f * d + d * d
    assert flops.layer_matmul_weights(cfg, "rwkv") == weights
    head = d * 65536
    wkv = 4 * 40 * 64 * 64           # each of 40 heads' 64 x 64 state
    assert flops.decode_flops(one_layer(cfg), [5]) == \
        2 * (weights + head) + wkv
    state = 2 * (40 * 64 * 64 * 4 + 2 * d * 2)    # read and written
    other = 2 * d * 2 + 7 * d * 2 + d * 4 + d * 4 + d * 4
    want = 2 * weights + other + 2 * head + 2 * d + 2 * d + state
    assert flops.decode_bytes(one_layer(cfg), [5]) == want


def test_full_models_and_roofline():
    yi = program("yi-6b")
    # Yi-6B's 6.06e9 parameters less its 0.26e9 embedding rows
    assert flops.matmul_weights(yi) == pytest.approx(5.80e9, rel=0.01)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    t = flops.least_seconds(flops.decode_flops(yi, [512] * 8),
                            flops.decode_bytes(yi, [512] * 8), peaks)
    # memory bound: about 11.3 GB of weights at 819 GB/s
    assert 0.0135 < t < 0.0150
    rw = program("rwkv6-3b")
    # 3.07e9 parameters less the 0.17e9 embedding rows
    assert flops.matmul_weights(rw) == pytest.approx(2.90e9, rel=0.01)


def test_unknown_block_kind_is_an_error():
    with pytest.raises(flops.Uncounted):
        flops.layer_matmul_weights({"d_model": 8, "d_ff": 8}, "moe")
    for kind in ("moe", "rec"):
        with pytest.raises(flops.Uncounted):
            flops.decode_bytes(dict(program("yi-6b"), block_pattern=[kind]),
                               [10])
    # an expert FFN would be counted as one dense FFN of d_ff: refused
    with pytest.raises(flops.Uncounted, match="expert"):
        flops.decode_flops(dict(program("yi-6b"), n_experts=8), [10])
