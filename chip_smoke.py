"""Smoke run of the main paths on TPU chips, at a model's published widths.

    python chip_smoke.py              # one chip: yi-6b serving, phases a-d
    python chip_smoke.py --chips 4    # four chips: sharded training only

One chip, yi-6b at full size (32 layers, d_model 4096, 32/4 heads, d_ff
11008, vocab 64000, bf16, random weights from ``--seed``):

  a. device: JAX's default backend must be the TPU, checked before any
     other work;
  b. serve-cli: ``repro.launch.serve.main`` on the dense KV backend;
     every request must complete;
  c. paged-kernel: ``ServingEngine`` on ``PagedJaxModelBackend`` with the
     Pallas decode kernel.  One decode step's logits are compared with the
     gather oracle's on the same state, the compiled decode must hold the
     kernel's ``tpu_custom_call``, then the same prompts are served;
  d. kernels: each Pallas kernel against its ``kernels/ref.py`` oracle at
     real widths.

Four chips (``--chips 4``): ``repro.launch.train`` on a 2x2 (data x model)
mesh at yi-6b widths, depth cut to 4 layers, once per sharding strategy.
The two plans compute the same steps, so their losses must agree.

Each phase prints one JSON line (seconds, compile seconds, persistent
cache hits, device memory, comparison errors).  The last line is
``{"ok": true, "device": {...}}``; any failure exits non-zero before it.
The compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else in
``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "yi-6b"
SLOTS = 8
REQUESTS = 16         # two admission waves through 8 slots
CACHE_LEN = 1024
PROMPT_LEN = 128      # one length: prefill compiles once per length
NEW_TOKENS = 32

# phase (c): kernel vs gather oracle, relative to the oracle's largest
# logit.  The two decode programs differ only in attention's rounding: the
# oracle rounds the softmax weights to bf16 before the PV product, the
# kernel keeps them in f32.  One bf16 rounding is 2^-8 = 3.9e-3 relative;
# 32 layers of bf16 residual stream carry it to the logits, so a few
# roundings' worth is the bound.
LOGIT_TOL = 2e-2
# phase (d), max error relative to the oracle's largest output.  bf16
# kernels (paged, flash) round their output to bf16 and the oracle also
# rounds its softmax weights: a few bf16 ulps.  f32 kernels (wkv, lru)
# sum in another order than the oracle over 2048 steps.
BF16_TOL = 2e-2
F32_TOL = 1e-3
# --chips 4: per-step losses of the two sharding plans.  Same math, but
# bf16 matmuls reduced in a different order (and over different shards)
# move each loss by bf16 noise; five AdamW steps at lr 3e-4 move the
# weights by at most 1.5e-3, too little to let the runs drift apart.
LOSS_TOL = 2e-2

COMPILE = {"seconds": 0.0, "hits": 0, "misses": 0}


def _listen(jax) -> None:
    """Sum backend compile seconds and count persistent-cache hits."""
    from jax import monitoring

    def duration(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            COMPILE["seconds"] += secs

    def count(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            COMPILE["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            COMPILE["misses"] += 1

    monitoring.register_event_duration_secs_listener(duration)
    monitoring.register_event_listener(count)


def _phase(jax, name: str, fn) -> dict:
    """Run one phase; print its JSON line and return it."""
    before = dict(COMPILE)
    t0 = time.perf_counter()
    row = {"phase": name, **fn()}
    gc.collect()           # engines hold cycles: free their arrays now
    row["seconds"] = time.perf_counter() - t0
    row["compile_seconds"] = COMPILE["seconds"] - before["seconds"]
    row["cache_hits"] = COMPILE["hits"] - before["hits"]
    row["cache_misses"] = COMPILE["misses"] - before["misses"]
    row["memory"] = {str(d): _mem(d) for d in jax.devices()}
    print(json.dumps(row), flush=True)
    return row


def _mem(device) -> dict:
    stats = device.memory_stats() or {}
    return {k: stats.get(k) for k in ("peak_bytes_in_use", "bytes_in_use",
                                      "bytes_limit")}


def _rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.isfinite(got).all(), "non-finite output"
    return float(np.abs(got - want).max() / np.abs(want).max())


def _prompts(vocab: int, seed: int) -> list:
    """The prompts ``serve.main`` draws for ``--seed``."""
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=PROMPT_LEN) for _ in range(REQUESTS)]


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def serve_cli(seed: int) -> dict:
    from repro.launch import serve
    rc = serve.main(["--arch", ARCH, "--slots", str(SLOTS),
                     "--requests", str(REQUESTS),
                     "--cache-len", str(CACHE_LEN),
                     "--prompt-len", str(PROMPT_LEN),
                     "--new-tokens", str(NEW_TOKENS), "--seed", str(seed)])
    assert rc == 0, rc
    return {"requests": REQUESTS}


def paged_kernel(jax, seed: int) -> dict:
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.models import api
    from repro.serving import PagedJaxModelBackend, ServingEngine

    cfg = get_config(ARCH)
    params = api.init(cfg, jax.random.PRNGKey(seed))
    # one spare slot of pages: the pool exists twice at peak (decode and
    # the admission page-in both write a new pool), ~0.6 GB each
    pb = PagedJaxModelBackend(cfg, params, CACHE_LEN, use_kernel=True,
                              slack_slots=1)
    prompts = _prompts(cfg.vocab, seed)

    # one decode step on a prefilled batch, kernel against oracle
    shard, _ = pb.init(SLOTS)
    firsts = pb.prefill_wave(prompts[:SLOTS])
    shard = pb.splice(shard, [(i, h) for i, (_, h) in enumerate(firsts)])
    pb._ensure_pages(shard)            # map each slot's next page, as decode does
    args = (params, jnp.asarray([[t] for t, _ in firsts], jnp.int32),
            shard.states, jnp.asarray(shard.table),
            jnp.asarray(shard.lengths))
    kernel = pb._decode.lower(*args).compile()   # the engine's own program
    assert "tpu_custom_call" in kernel.as_text(), \
        "paged decode holds no Pallas kernel"
    oracle = jax.jit(api.make_paged_decode_fn(cfg, use_kernel=False)
                     ).lower(*args).compile()
    got = kernel(*args)[0]
    want = oracle(*args)[0]
    err = _rel_err(got, want)
    top1 = float(np.mean(np.argmax(np.asarray(got), -1)
                         == np.argmax(np.asarray(want), -1)))
    assert err <= LOGIT_TOL, f"kernel logits off the oracle by {err}"
    del shard, firsts, args, got, want

    # serve the prompts through the engine on the kernel path
    eng = ServingEngine(cfg, params, n_slots=SLOTS, cache_len=CACHE_LEN,
                        backend=pb)
    for p in prompts:
        eng.submit(p, NEW_TOKENS)
    done = eng.run(max_steps=REQUESTS * NEW_TOKENS * 4)
    assert len(done) == REQUESTS, (len(done), REQUESTS)
    assert all(len(r.out_tokens) == NEW_TOKENS for r in done)
    return {"requests": len(done), "logit_rel_err": err,
            "logit_tol": LOGIT_TOL, "top1_agree": top1,
            "pool_page_writes": pb.stats["pool_page_writes"]}


def kernels(jax) -> dict:
    import jax.numpy as jnp
    from repro.kernels import (flash_attention, paged_attention, ref, rglru,
                               rwkv6)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 16))

    def normal(shape, dtype=jnp.float32, scale=1.0):
        return (jax.random.normal(next(keys), shape) * scale).astype(dtype)

    errs: dict = {}

    def check(name, got, want, tol):
        errs[name] = _rel_err(got, want)
        assert errs[name] <= tol, f"{name}: {errs[name]} > {tol}"

    hi = jax.default_matmul_precision("highest")   # oracles in full f32

    # paged decode at yi-6b's heads: 8 slots x 1024 tokens, 16-token pages
    B, K, g, hd, ps, pps = SLOTS, 4, 8, 128, 16, CACHE_LEN // 16
    P = 1 + (SLOTS + 1) * pps
    q = normal((B, K, g, hd), jnp.bfloat16)
    kp = normal((P, ps, K, hd), jnp.bfloat16)
    vp = normal((P, ps, K, hd), jnp.bfloat16)
    lengths = jax.random.randint(next(keys), (B,), 1, CACHE_LEN + 1)
    pages = jax.random.permutation(next(keys), jnp.arange(1, P))
    used = jnp.arange(pps)[None, :] * ps < lengths[:, None]
    tables = jnp.where(used, pages[:B * pps].reshape(B, pps), 0)
    got = paged_attention.paged_attn(q, kp, vp, tables, lengths,
                                     scale=hd ** -0.5, interpret=False)
    with hi:
        want = ref.paged_sdpa_ref(q, kp, vp, tables, lengths,
                                  scale=hd ** -0.5)
    check("paged_attn", got, want, BF16_TOL)

    # rwkv6-3b's time mix: 40 heads of 64
    S, H, hd = 2048, 40, 64
    r, k, v = (normal((1, S, H, hd), scale=0.5) for _ in range(3))
    w = jnp.exp(-jnp.exp(normal((1, S, H, hd), scale=0.5) - 1.0))
    u = normal((H, hd), scale=0.5)
    y, sfin = rwkv6.wkv(r, k, v, w, u, interpret=False)
    with hi:
        y_ref, s_ref = ref.wkv_ref(r, k, v, w, u)
    check("wkv_y", y, y_ref, F32_TOL)
    check("wkv_state", sfin, s_ref, F32_TOL)

    # RG-LRU scan at width 2560
    a = jax.nn.sigmoid(normal((1, S, 2560)) + 2.0)
    b = normal((1, S, 2560))
    check("lru_scan", rglru.lru_scan(a, b, interpret=False),
          ref.lru_scan_ref(a, b), F32_TOL)

    # flash attention at yi-6b's heads, causal, S=2048
    q = normal((1, S, 32, 128), jnp.bfloat16)
    k = normal((1, S, 4, 128), jnp.bfloat16)
    v = normal((1, S, 4, 128), jnp.bfloat16)
    got = flash_attention.mha(q, k, v, scale=128 ** -0.5, interpret=False)
    with hi:
        want = ref.sdpa_ref(q, k, v, scale=128 ** -0.5)
    check("flash_mha", got, want, BF16_TOL)
    return {"rel_err": errs, "tol": {"bf16": BF16_TOL, "f32": F32_TOL}}


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def train_mesh(jax, seed: int) -> dict:
    from repro.launch import train
    argv = ["--arch", ARCH, "--layers", "4", "--mesh", "2x2",
            "--batch", "8", "--seq", "2048", "--steps", "5",
            "--seed", str(seed)]
    runs = {}
    for strategy in ("bubbles", "simple"):
        out = train.run(argv + ["--strategy", strategy])
        gc.collect()
        out["peak_bytes"] = {str(d): _mem(d)["peak_bytes_in_use"]
                             for d in jax.devices()}
        # no device holds one whole copy of the parameters + optimizer
        assert max(out["state_bytes"].values()) < out["state_total"], out
        runs[strategy] = out
    diffs = [abs(a - b) for a, b in zip(runs["bubbles"]["losses"],
                                        runs["simple"]["losses"])]
    assert len(diffs) == 5 and max(diffs) <= LOSS_TOL, diffs
    return {"runs": runs, "max_loss_diff": max(diffs),
            "loss_tol": LOSS_TOL}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the serving phases on one chip; 4: only the "
                         "sharded training phase on a 2x2 mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        sys.exit(f"chip_smoke.py: no {SRC / 'repro'}; run it from a "
                 "checkout of the repository")
    sys.path.insert(0, str(SRC))

    import jax
    platform = jax.default_backend()
    if platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU; JAX's default backend is "
                 f"{platform!r}")
    n = len(jax.devices())
    if n < args.chips:
        sys.exit(f"--chips {args.chips}: JAX sees {n} device(s)")

    from repro.launch.compile_cache import use_compile_cache
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": n}
    print(json.dumps({"phase": "device", **device,
                      "compile_cache": use_compile_cache()}), flush=True)
    _listen(jax)

    if args.chips == 4:
        _phase(jax, "train-2x2", lambda: train_mesh(jax, args.seed))
    else:
        _phase(jax, "serve-cli", lambda: serve_cli(args.seed))
        _phase(jax, "paged-kernel", lambda: paged_kernel(jax, args.seed))
        _phase(jax, "kernels", lambda: kernels(jax))

    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
