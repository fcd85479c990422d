"""Compile rehearsals of the Pallas kernels for a described TPU v5e.

Interpret mode (every other kernel test) checks the math but not what the
chip's compiler accepts: block shapes off the (sublane, lane) tiling,
primitives Mosaic cannot lower (``dynamic_slice`` of a value), or more
VMEM than a kernel may use.  These tests compile each kernel at the
widths ``chip_smoke.py`` runs it on the chip — paged decode at yi-6b's
heads, WKV at rwkv6-3b's 40x64 heads, the RG-LRU scan at width 2560,
flash attention at yi-6b's heads — for one chip of a ``v5e:2x2``
topology described without the chip, and print ``memory_analysis()``.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and pytest-xdist
workers import every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention, paged_attention, rglru, rwkv6


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent cache
    # but not read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    print(compiled.memory_analysis())
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_paged_attention_yi6b(one_chip):
    # yi-6b decode: 8 slots x 1024-token cache in 16-token pages, 32 query
    # heads over 4 KV heads of 128, bf16 pool
    B, K, g, hd, ps, pps = 8, 4, 8, 128, 16, 64
    P = 1 + 9 * pps
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)  # noqa
    _compile(lambda q, k, v, t, n: paged_attention.paged_attn(
        q, k, v, t, n, scale=hd ** -0.5, interpret=False),
        sds((B, K, g, hd), jnp.bfloat16), sds((P, ps, K, hd), jnp.bfloat16),
        sds((P, ps, K, hd), jnp.bfloat16), sds((B, pps), jnp.int32),
        sds((B,), jnp.int32))


def test_wkv_rwkv6_3b(one_chip):
    B, S, H, hd = 1, 2048, 40, 64
    sds = lambda s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)  # noqa
    x = sds((B, S, H, hd))
    _compile(lambda r, k, v, w, u: rwkv6.wkv(r, k, v, w, u, interpret=False),
             x, x, x, x, sds((H, hd)))


def test_lru_scan_width_2560(one_chip):
    x = jax.ShapeDtypeStruct((1, 2048, 2560), jnp.float32, sharding=one_chip)
    _compile(lambda a, b: rglru.lru_scan(a, b, interpret=False), x, x)


def test_flash_attention_yi6b(one_chip):
    B, S, H, K, hd = 1, 2048, 32, 4, 128
    sds = lambda s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)  # noqa
    _compile(lambda q, k, v: flash_attention.mha(
        q, k, v, scale=hd ** -0.5, interpret=False),
        sds((B, S, H, hd)), sds((B, S, K, hd)), sds((B, S, K, hd)))
