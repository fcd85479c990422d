"""Where the entry points keep JAX's persistent compile cache.

Each case runs in a child process on the CPU: the cache directory is
process-wide JAX config, and the child decides it before its first
compile, as an entry point does.
"""

import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

CHILD = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import CHECKOUT_CACHE, use_compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
print(use_compile_cache())
print(CHECKOUT_CACHE)
if {compile}:
    jax.jit(lambda x: x * 2 + 1)(jnp.arange(4.0)).block_until_ready()
"""


def _child(env_dir, compile_: bool) -> list[str]:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c",
                        CHILD.format(compile=compile_)],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.split()


def test_env_var_places_the_cache(tmp_path):
    used, _ = _child(tmp_path, compile_=True)
    assert used == str(tmp_path)
    assert any(tmp_path.iterdir()), "nothing written to the named directory"


def test_default_is_the_checkout():
    used, checkout = _child(None, compile_=False)
    assert used == checkout == str(ROOT / ".jax_cache")
