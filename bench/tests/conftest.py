"""A benchmark checkout in a temporary directory, with a tiny cell that
runs on the CPU: for the tests that drive a whole run."""

import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
if str(REPO / "src") not in sys.path:
    sys.path.insert(0, str(REPO / "src"))

TINY_CONFIG = {
    "name": "tiny-llama", "source": "a test's own", "reduced": [],
    "program": {"name": "tiny", "family": "dense", "n_layers": 2,
                "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "d_ff": 128,
                "vocab": 256, "head_dim": 16, "rope_theta": 10000.0,
                "param_dtype": "bfloat16", "compute_dtype": "bfloat16"},
    "reference": "llama",
    "serving": {"n_slots": 4, "cache_len": 128, "page_size": 16,
                "slack_slots": 1},
    "check": {"gap_limit": 0.03, "tokens": 150, "requests": 8,
              "min_tokens": 10},
}

TINY_TRAFFIC = {
    "process": "open_loop", "arrivals": {"rate": 4.0}, "ramp_s": 0.5,
    "trace_s": 1.0, "round_to": 16, "warm_waves": [1],
    "classes": {"chat": {
        "share": 1.0,
        "prompt": {"median": 24, "sigma": 0.5, "lo": 16, "hi": 32},
        "output": {"median": 8, "sigma": 0.5, "lo": 4, "hi": 16}}},
}


@pytest.fixture
def checkout(tmp_path):
    """``tmp_path`` laid out as a checkout: BENCHMARK.json with one tiny
    cell, a copy of ``bench/`` and the program's ``src/``."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (tmp_path / "src").symlink_to(REPO / "src")
    (tmp_path / "bench/configs/tiny-llama.json").write_text(
        json.dumps(TINY_CONFIG))
    (tmp_path / "bench/traffic/tiny_chat.json").write_text(
        json.dumps(TINY_TRAFFIC))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny-llama", "source": "a test's own",
                        "file": "bench/configs/tiny-llama.json",
                        "reduced": [], "why": "runs on the CPU"}]
    spec["workloads"] = [{"name": "tiny.chat", "config": "tiny-llama",
                          "traffic": "tiny_chat", "chips": 1,
                          "why": "runs on the CPU"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.chat"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


@pytest.fixture
def compile_cache(tmp_path):
    """A compile cache of the test's own; JAX's settings restored after."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield tmp_path / "jax_cache"
    for n, v in saved.items():
        jax.config.update(n, v)
    cc.reset_cache()
