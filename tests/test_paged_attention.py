"""Paged-KV decode: kernel oracle checks, backend stream parity, and the
zero-copy migration property.

Four layers, cheapest first:

* **kernel** — ``kernels.paged_attention.paged_attn`` (interpret mode on
  CPU) against both oracles: the paged gather oracle
  (``ref.paged_sdpa_ref``) across GQA ratios / sliding window / ragged
  per-slot page counts, and the *dense* ``ref.sdpa_ref`` on each slot's
  contiguous history — proving the block-table indirection is invisible.
* **backend parity** — ``PagedJaxModelBackend`` vs ``JaxModelBackend``
  driven through prefill → splice → decode on reduced zoo configs
  (transformer and rwkv): identical token streams, including through the
  lazy page-allocation boundary (the first decode that crosses into an
  unmapped page) and with the Pallas kernel swapped in.
* **engine property** — a single-host ``ServingEngine`` trace with gang
  regeneration (park → re-splice mid-flight): the paged engine's streams
  equal the dense engine's token for token while its KV pool is never
  copied (``pool_copies == 0``) — every migration was a block-table edit
  (``table_splices > 0``).
* **batch-axis spec** — ``api.batch_axis_spec`` unit tests, including the
  regression the spec exists for: a genuine 1-D ``(B,)`` per-slot leaf,
  which the old ``ndim >= 2`` heuristic silently skipped on splice
  (resuming a request with another request's state had any model carried
  one).
* **agentic prefix reuse** — a tool-calling session that sleeps
  mid-decode and wakes with its prefix KV pages still resident resumes
  as a block-table re-point (``table_splices > 0``, ``pool_copies == 0``,
  no re-prefill), and its stream is bit-identical to a cold wake whose KV
  was stale-evicted and re-prefilled from the token history.
* **jitted prefill** — a second admission wave of one shape compiles
  nothing and loads nothing from the compile cache, and serves what the
  model's prefill run op by op serves.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.kernels import paged_attention, ref
from repro.models import api
from repro.serving import (JaxModelBackend, PagedJaxModelBackend,
                           ServingEngine)

PS = 8            # page size
PPS = 4           # pages per slot


def _paged_case(rng, B, K, g, hd, lengths):
    """Random pool + ragged block tables: slot b owns ceil(len/PS) pages
    at shuffled pool indices, unused table entries 0 (the trash page)."""
    q = jnp.asarray(rng.standard_normal((B, K, g, hd)), jnp.float32)
    P = 1 + B * PPS
    k_pool = jnp.asarray(rng.standard_normal((P, PS, K, hd)), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((P, PS, K, hd)), jnp.float32)
    tables = np.zeros((B, PPS), np.int32)
    perm = rng.permutation(np.arange(1, P))
    used = 0
    for b, ln in enumerate(lengths):
        n = -(-ln // PS) if ln else 0
        tables[b, :n] = perm[used:used + n]
        used += n
    return q, k_pool, v_pool, jnp.asarray(tables), \
        jnp.asarray(np.asarray(lengths, np.int32))


class TestPagedKernel:
    @pytest.mark.parametrize("K,g", [(4, 1), (2, 2), (1, 8)])
    @pytest.mark.parametrize("window", [None, 6])
    def test_matches_paged_oracle(self, K, g, window):
        rng = np.random.default_rng(0)
        lengths = [5, 8, 17, 1]                    # ragged page counts
        q, kp, vp, tbl, ln = _paged_case(rng, 4, K, g, 16, lengths)
        got = paged_attention.paged_attn(q, kp, vp, tbl, ln,
                                         window=window, scale=0.25,
                                         interpret=True)
        want = ref.paged_sdpa_ref(q, kp, vp, tbl, ln,
                                  window=window, scale=0.25)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    def test_matches_dense_oracle_per_slot(self):
        """Gather each slot's pages back into a contiguous (1, L, H, hd)
        history and run plain causal SDPA: the paged kernel's answer is
        the dense answer's last row — the indirection is invisible."""
        K, g, hd = 2, 2, 16
        rng = np.random.default_rng(1)
        lengths = [5, 8, 17, 32]
        q, kp, vp, tbl, ln = _paged_case(rng, 4, K, g, hd, lengths)
        got = paged_attention.paged_attn(q, kp, vp, tbl, ln,
                                         scale=hd ** -0.5, interpret=True)
        for b, L in enumerate(lengths):
            hist_k = np.asarray(kp[tbl[b]]).reshape(-1, K, hd)[:L]
            hist_v = np.asarray(vp[tbl[b]]).reshape(-1, K, hd)[:L]
            # GQA: expand K kv heads to H = K*g query heads
            qh = np.asarray(q[b]).reshape(1, 1, K * g, hd)
            kh = np.repeat(hist_k, g, axis=1)[None]
            vh = np.repeat(hist_v, g, axis=1)[None]
            # query is the LAST position of the history: pad q to L rows
            qfull = np.concatenate(
                [np.zeros((1, L - 1, K * g, hd), np.float32), qh], axis=1)
            want = ref.sdpa_ref(jnp.asarray(qfull), jnp.asarray(kh),
                                jnp.asarray(vh), scale=hd ** -0.5)[0, -1]
            np.testing.assert_allclose(
                np.asarray(got[b]).reshape(K * g, hd), want,
                atol=2e-5, rtol=2e-5)

    def test_free_slot_rows_finite(self):
        """lengths == 0 rows (freed slots decoding into the trash page)
        must produce finite garbage, exactly like the dense path."""
        rng = np.random.default_rng(2)
        q, kp, vp, tbl, ln = _paged_case(rng, 3, 2, 2, 16, [7, 0, 0])
        got = paged_attention.paged_attn(q, kp, vp, tbl, ln,
                                         interpret=True)
        assert np.isfinite(np.asarray(got)).all()


def _bstreams(cfg, params, backend_cls, steps=6, **kw):
    """prefill → splice → decode loop straight through a backend (no
    engine): returns the per-slot greedy streams."""
    backend = backend_cls(cfg, params, 32, **kw)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 97, 6) for _ in range(3)]
    states, tokens = backend.init(3)
    out = [[] for _ in range(3)]
    for i, (tok, h) in enumerate(backend.prefill_wave(prompts)):
        tokens[i, 0] = tok
        out[i].append(tok)
        states = backend.splice(states, [(i, h)])
    for _ in range(steps):
        nxt, states = backend.decode(tokens, states)
        for i in range(3):
            out[i].append(int(nxt[i]))
            tokens[i, 0] = nxt[i]
    return [tuple(s) for s in out], backend


class TestBackendParity:
    @pytest.mark.parametrize("arch", ["yi-6b", "rwkv6-3b"])
    def test_paged_streams_equal_dense(self, arch):
        """6 decode steps crosses a page boundary (prompt 6 + 6 > 8 = one
        page), so the lazy-allocation path is on the line too."""
        cfg = get_config(arch).reduced(vocab=97)
        params = api.init(cfg, jax.random.PRNGKey(0))
        dense, _ = _bstreams(cfg, params, JaxModelBackend)
        paged, pb = _bstreams(cfg, params, PagedJaxModelBackend,
                              page_size=PS)
        assert dense == paged
        assert pb.stats["pool_copies"] == 0

    def test_kernel_path_streams_equal_dense(self):
        """The Pallas kernel (interpret mode) behind the paged backend:
        same greedy stream as the dense backend."""
        cfg = get_config("yi-6b").reduced(vocab=97)
        params = api.init(cfg, jax.random.PRNGKey(0))
        dense, _ = _bstreams(cfg, params, JaxModelBackend, steps=3)
        paged, _ = _bstreams(cfg, params, PagedJaxModelBackend, steps=3,
                             page_size=PS, use_kernel=True)
        assert dense == paged


def _engine_run(cfg, params, backend):
    eng = ServingEngine(cfg, params, n_slots=8, cache_len=32,
                        backend=backend)
    rng = np.random.default_rng(0)
    gangs = ["g0", "g1"]
    n = 12
    for i in range(n):
        eng.submit(rng.integers(1, 97, 6), int(rng.integers(2, 8)),
                   gang=gangs[i % 2] if i < 8 else None)
    steps = 0
    while not eng._drained() and steps < 2000:
        eng.step()
        steps += 1
        if steps % 3 == 0:
            eng.regenerate_gang(gangs[(steps // 3) % 2])
    assert len(eng.completed) == n
    return eng, {r.rid: tuple(r.out_tokens) for r in eng.completed}


class TestEngineZeroCopy:
    def test_park_splice_is_metadata_only(self):
        """Single-host trace with rolling gang regeneration: every parked
        request resumes mid-flight.  On the paged backend those resumes
        are block-table edits — the KV pool is never copied — and the
        streams still match the dense backend token for token."""
        cfg = get_config("yi-6b").reduced(vocab=97)
        params = api.init(cfg, jax.random.PRNGKey(0))
        _, dense = _engine_run(cfg, params,
                               JaxModelBackend(cfg, params, 32))
        pb = PagedJaxModelBackend(cfg, params, 32, page_size=PS)
        ep, paged = _engine_run(cfg, params, pb)
        assert dense == paged
        assert ep.stats.kv_parks > 0              # the path really ran
        assert pb.stats["table_splices"] > 0      # resumes were metadata
        assert pb.stats["pool_copies"] == 0       # ... and ONLY metadata
        assert pb.stats["pool_page_writes"] > 0   # prefills did page in


class TestAgenticPrefixReuse:
    @staticmethod
    def _session_run(cfg, params, **kw):
        pb = PagedJaxModelBackend(cfg, params, 32, page_size=PS)
        eng = ServingEngine(cfg, params, n_slots=4, cache_len=32,
                            backend=pb, **kw)
        rng = np.random.default_rng(3)
        # prompt 6 + turn 1's 4 tokens cross the PS=8 page boundary, so
        # the parked handle spans two pages when the session sleeps
        eng.submit(rng.integers(1, 97, 6), 10, tool_calls=((4, 5),))
        eng.run(max_steps=500)
        assert len(eng.completed) == 1
        return eng, pb, tuple(eng.completed[0].out_tokens)

    def test_warm_wake_is_table_repoint_cold_wake_is_bit_identical(self):
        """A woken session whose prefix KV pages are still resident skips
        prefill entirely: the resume is a block-table re-point with zero
        pool copies.  Forcing the same session through a stale eviction
        (``session_ttl`` shorter than the think gap) rebuilds its KV from
        the token history — and must produce the bit-identical stream."""
        cfg = get_config("yi-6b").reduced(vocab=97)
        params = api.init(cfg, jax.random.PRNGKey(0))
        warm_eng, warm_pb, warm = self._session_run(cfg, params)
        c = warm_eng.counters()
        assert c["sleeps"] == c["wakes"] == 1
        assert c["wake_reprefills"] == 0          # prefix pages were resident
        assert warm_eng.stats.prefills == 1       # the one fresh prefill
        assert warm_pb.stats["table_splices"] > 0  # wake was metadata
        assert warm_pb.stats["pool_copies"] == 0   # ... and ONLY metadata
        cold_eng, cold_pb, cold = self._session_run(cfg, params,
                                                    session_ttl=2)
        cc = cold_eng.counters()
        assert cc["stale_evictions"] == 1          # KV dropped past the TTL
        assert cc["wake_reprefills"] == 1          # wake rebuilt it
        assert cold == warm                        # bit-identical stream


class TestBatchAxisSpec:
    @staticmethod
    def _init(n):
        return {"cache": jnp.zeros((2, n, 8)),      # reps-stacked, axis 1
                "flag": jnp.zeros((n,)),            # 1-D per-slot leaf
                "pool": jnp.zeros((7, 4)),          # batch-free
                "scalar": jnp.zeros(())}

    def test_axes_inferred(self):
        axes = api.batch_axis_spec(self._init)
        assert axes == {"cache": 1, "flag": 0, "pool": -1, "scalar": -1}

    def test_multi_axis_leaf_rejected(self):
        with pytest.raises(ValueError, match="varies on 2 axes"):
            api.batch_axis_spec(lambda n: {"bad": jnp.zeros((n, n))})

    def test_1d_leaf_spliced_not_skipped(self):
        """THE regression the spec fixes: the old ``b.ndim >= 2`` guard
        returned 1-D leaves untouched, so a ``(B,)`` per-slot leaf kept
        the evicted request's value after a splice.  The spec-driven
        write (the exact ``JaxModelBackend.splice`` traversal) updates
        it."""
        axes = api.batch_axis_spec(self._init)
        states = {"cache": jnp.zeros((2, 4, 8)),
                  "flag": jnp.arange(4.0),
                  "pool": jnp.zeros((7, 4)), "scalar": jnp.zeros(())}
        one = {"cache": jnp.ones((2, 1, 8)), "flag": jnp.full((1,), 9.0),
               "pool": jnp.zeros((7, 4)), "scalar": jnp.zeros(())}
        slots = jnp.asarray([2])

        def write(ax, b, new):
            if ax < 0:
                return b
            idx = (slice(None),) * ax + (slots,)
            return b.at[idx].set(jnp.concatenate([new], axis=ax))

        out = jax.tree.map(write, axes, states, one)
        assert out["flag"][2] == 9.0              # heuristic left this 2.0
        assert out["cache"][:, 2].sum() == 16.0
        assert (out["pool"] == states["pool"]).all()

    def test_model_zoo_states_all_resolve(self):
        """Every zoo decode state must yield a spec (no multi-axis leaf,
        attention/recurrent alike) — the dense backend builds this in its
        constructor, so a failure here is a backend constructor failure."""
        from repro.models import lm
        for arch in ("yi-6b", "rwkv6-3b", "recurrentgemma-9b"):
            cfg = get_config(arch).reduced(vocab=97)
            axes = api.batch_axis_spec(
                lambda n, c=cfg: lm.init_state(c, n, 32))
            leaves = jax.tree.leaves(axes)
            assert leaves and all(a in (-1, 0, 1) for a in leaves), \
                (arch, leaves)


PREFILL_CHILD = """
import json, numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.core import trace
from repro.models import api
from repro.serving import PagedJaxModelBackend
jax.config.update("jax_compilation_cache_dir", {cache!r})
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
cfg = get_config({arch!r}).reduced(vocab=97, param_dtype="bfloat16",
                                   compute_dtype="bfloat16")
params = api.init(cfg, jax.random.PRNGKey(0))
pb = PagedJaxModelBackend(cfg, params, 32, page_size=8)
rng = np.random.default_rng(3)
prompts = [rng.integers(1, 97, 12) for _ in range(2)]
log = trace.SpanLog()
trace.attach(log)
pb.prefill_wave(prompts)
served = pb.prefill_wave(prompts)        # the same (wave size, length)
trace.detach()
logits, st = api.make_prefill_fn(cfg, 32)(
    params, {{"tokens": jnp.asarray(np.stack(prompts))}})
gap = scale = 0.0
for i, (_, h) in enumerate(served):
    want = pb._fresh_handle(st, i, 12)
    pairs = zip(jax.tree.leaves((h["kv"], h["leaves"])),
                jax.tree.leaves((want["kv"], want["leaves"])))
    for a, b in pairs:
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        gap = max(gap, float(np.abs(a - b).max()))
        scale = max(scale, float(np.abs(b).max()))
print(json.dumps({{
    "forward": [r[4] for r in log.records if r[0] == "prefill.forward"],
    "tokens": [t for t, _ in served],
    "eager": np.asarray(jnp.argmax(logits, axis=-1)).tolist(),
    "gap": gap, "scale": scale}}))
"""


class TestJittedPrefill:
    @pytest.mark.parametrize("arch", ["yi-6b", "rwkv6-3b"])
    def test_second_wave_of_a_shape_neither_compiles_nor_loads(
            self, arch, tmp_path):
        """An eager prefill re-traces its layer scan on every wave and
        reads the program back from the compile cache; the jitted one
        finds it in memory.  In a child: the compile cache is process-wide
        JAX config."""
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=str(Path(__file__).resolve().parents[1]
                                  / "src"))
        r = subprocess.run(
            [sys.executable, "-c",
             PREFILL_CHILD.format(cache=str(tmp_path), arch=arch)],
            env=env, capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        got = json.loads(r.stdout.strip().splitlines()[-1])
        first, again = got["forward"]
        assert first.get("compiles", 0) >= 1       # the counter is live
        assert again.get("compiles", 0) == 0
        assert again.get("cache_loads", 0) == 0
        assert got["tokens"] == got["eager"]
        assert got["scale"] > 0
        assert got["gap"] <= 2 ** -6 * max(got["scale"], 1.0)
