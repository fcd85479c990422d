"""Where JAX's persistent compilation cache lives, decided in one place.

Entry points (``launch/serve.py``, ``launch/train.py``, ``chip_smoke.py``)
call :func:`use_compile_cache` before their first compile, so a second run
of the same program finds its compiled code instead of compiling again.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
  sets no other directory.
* unset: the cache goes to ``<checkout>/.jax_cache`` (gitignored).  The
  path is fixed — never a temp name, a pid or the time — because a cache
  whose directory moves is never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax  # deferred: importing repro.launch must not import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return jax.config.jax_compilation_cache_dir
