"""Golden-trace regression tests for the serving engine.

The simulator's golden traces (``tests/test_golden.py``) pin the
scheduler's behaviour on the paper's workloads; these pin the *serving*
stack — stub-backend decode streams plus the engine/scheduler counter
ledger — per engine mode and topology, single-host and multi-host:

* ``single_skew`` — the PR 3 skewed-gang workload on 8 slots, in both
  ``admission`` and ``runtime`` modes;
* ``single_churn`` — gang regeneration (KV park + batched splice) under
  steal traffic;
* ``multihost_skew`` — the pod-sharded fleet (2 pods x 2 hosts), with the
  DCN-priced cost table (``dcn``) and the flat-ranking/DCN-billed naive
  engine (``naive``, which also keeps the flat machine-wide rebalance
  mode — a DCN-naive engine does not know hosts exist);
* ``hbm_pressure`` — per-page-group HBM budgets, capacity-``aware`` vs
  capacity-``blind`` (rebalance mode pinned flat in both, isolating the
  capacity variable — matching ``benchmarks/serve_gangs.py``);
* ``dcn_rebalance`` — the DCN-priced rebalance path: admission-bound
  within-host skew on every host; ``local`` quotes re-spreads through the
  boundary-priced estimate and buys host-local page shuffles, ``flat``
  keeps the flat-quoted machine-wide deal and pays its level-table tolls
  as admission freezes on the receiving page groups;
* ``open_loop`` — the PR 6 open-loop SLA workload (seeded Poisson
  arrivals, heavy-tailed lengths, interactive/standard/batch classes) on
  8 slots x 2 hosts: ``fifo`` holds slots in arrival order, ``sla`` runs
  WDRR admission + multilevel-feedback demotion + batch-gang preemption
  (the snapshot additionally pins the preemption/demotion counters);
* ``agentic_tool`` — tool calls mid-decode on a single host (agentic
  singles, an agentic gang, plain backlog): ``sleep`` parks KV and frees
  the slot at each marker, ``hold`` keeps the slot through the think gap
  — the snapshot pins the sleep/wake/affinity counters and the shared
  digest proves blocking policy never changes tokens;
* ``agentic_paged`` — a multi-turn session on the paged jax backend: the
  woken session's prefix KV pages are still resident, so every wake is a
  block-table re-point (``table_splices``) with **zero** pool copies and
  no re-prefill.  Its float-model tokens depend on the JAX build, so its
  ``streams`` entry pins paged == dense on the same trace, not a digest.

Each snapshot records the engine step count, a digest of every completed
request's full decode stream (the stub backend hashes token history, so
*any* KV mishandling — lost splice, stale slot, wrong-slot write, a
budget overcommit — changes the digest), and the counters that describe
the schedule.  Everything is deterministic: prompts come from a seeded
generator and the engine has no RNG.

To regenerate after an *intentional* behaviour change::

    PYTHONPATH=src python tests/test_serving_golden.py

and paste the printed dict over ``GOLDEN``.  CI's golden-drift job runs::

    PYTHONPATH=src python tests/test_serving_golden.py --check

which regenerates every snapshot and fails (exit 1, printing the drifted
entries) if any differs from the committed dict.
"""

import hashlib

import numpy as np
import pytest

from repro.core import reset_ids, trace
from repro.core.scheduler import StealCostModel  # noqa: F401  (re-export)
from repro.serving import (FLAT_SERVE_COST, SERVE_COST, ServingEngine,
                           StubModelBackend)

COUNTER_KEYS = ("steals", "steal_refusals", "rebalances", "kv_migrations",
                "kv_page_moves", "kv_host_moves", "kv_parks", "prefills",
                "hbm_slot_waits", "hbm_refusals")


def _submit(eng: ServingEngine, spec, seed: int = 0) -> int:
    """spec: (gang, count, prio, home, new_tokens); returns count."""
    rng = np.random.default_rng(seed)
    n = 0
    for gang, count, prio, home, new_tokens in spec:
        for _ in range(count):
            eng.submit(rng.integers(1, 250, 8), new_tokens, prio=prio,
                       gang=gang, home=home)
            n += 1
    return n


def _streams(eng: ServingEngine) -> list:
    """Every completed request's decode stream, in request-id order."""
    return sorted((r.rid, tuple(r.out_tokens)) for r in eng.completed)


def _snapshot(eng: ServingEngine, n: int) -> dict:
    """Snapshot streams + ledger for a drained engine."""
    assert len(eng.completed) == n, (len(eng.completed), n)
    digest = hashlib.blake2b(repr(_streams(eng)).encode(),
                             digest_size=8).hexdigest()
    c = eng.counters()
    snap = {"steps": eng.steps, "streams": digest}
    snap.update({k: c[k] for k in COUNTER_KEYS})
    snap["stall_steps"] = round(c["stall_steps"], 4)
    return snap


def _drive(eng: ServingEngine, n: int, regen=()) -> dict:
    """Run to drain (bounded), snapshot streams + ledger."""
    regen = dict(regen)                     # step -> gang to regenerate
    steps = 0
    while not eng._drained() and steps < 8000:
        eng.step()
        steps += 1
        gang = regen.get(steps)
        if gang is not None:
            eng.regenerate_gang(gang)
    return _snapshot(eng, n)


SINGLE_SKEW = [("fat", 16, 0, None, 12), ("a", 2, 2, None, 12),
               ("b", 1, 1, None, 12), (None, 2, 1, None, 12)]
SINGLE_CHURN = [(f"g{i}", 2, i % 3, None, 12) for i in range(8)]
# the benchmark's skewed-pod shape: heavy fat threads on host0 tempt a
# flat-cost victim ranking across the DCN while light local backlog waits
MULTI_SKEW = ([("fat", 16, 0, "host0", 28)] +
              [(f"h{h}g{g}", 8, 0, f"page{2 * h}", 12)
               for h in range(1, 4) for g in range(2)])
HBM = [("fat", 24, 0, "host0", 10), (None, 6, 1, "host1", 6)]
# the benchmark's dcn-rebalance shape: short small requests (admission-
# bound) with every host's own backlog homed on its FIRST page list
DCN_REB = ([("fat", 12, 0, "host0", 24)] +
           [(f"h{h}g{g}", 8, 0, f"page{2 * h}", 4)
            for h in range(4) for g in range(2)])


def build(case: str, variant: str) -> tuple[ServingEngine, list, tuple]:
    stub = StubModelBackend()
    if case == "single_skew":
        eng = ServingEngine(None, None, n_slots=8, backend=stub,
                            mode=variant)
        return eng, SINGLE_SKEW, ()
    if case == "single_churn":
        eng = ServingEngine(None, None, n_slots=8, backend=stub,
                            mode=variant)
        return eng, SINGLE_CHURN, ((4, "g1"), (8, "g5"))
    if case == "multihost_skew":
        cost, bill = (SERVE_COST, None) if variant == "dcn" else \
            (FLAT_SERVE_COST, SERVE_COST)
        eng = ServingEngine(None, None, n_slots=32, pods=2, hosts=2,
                            backend=stub, cost_model=cost, bill_model=bill,
                            dcn_rebalance=(variant == "dcn"))
        return eng, MULTI_SKEW, ()
    if case == "dcn_rebalance":
        eng = ServingEngine(None, None, n_slots=32, pods=2, hosts=2,
                            backend=stub, cost_model=SERVE_COST,
                            dcn_rebalance=(variant == "local"))
        return eng, DCN_REB, ()
    assert case == "hbm_pressure", case
    eng = ServingEngine(None, None, n_slots=16, hosts=2, backend=stub,
                        hbm_budget=2.0, kv_bytes=1.0,
                        capacity_aware=(variant == "aware"),
                        dcn_rebalance=False)
    return eng, HBM, ()


def simulate(case: str, variant: str) -> dict:
    reset_ids()
    if case == "open_loop":
        # open-loop: arrivals come from the seeded workload trace and are
        # submitted at their arrival steps by drive(), not batched up front
        from repro.serving import SLA_CLASSES, drive, make_trace
        trace = make_trace(steps=48, rate=1.2, seed=3)
        stub = StubModelBackend()
        if variant == "sla":
            eng = ServingEngine(None, None, n_slots=8, group=2, hosts=2,
                                backend=stub, sla_classes=SLA_CLASSES,
                                preempt=True, preempt_cooldown=4)
        else:
            assert variant == "fifo", variant
            eng = ServingEngine(None, None, n_slots=8, group=2, hosts=2,
                                backend=stub, mode="admission")
        drive(eng, trace)
        snap = _snapshot(eng, len(trace))
        c = eng.counters()
        snap.update({k: c[k] for k in ("preemptions", "preempt_parks",
                                       "demotions")})
        return snap
    if case == "agentic_tool":
        # tool calls mid-decode, single host: agentic singles, one agentic
        # gang (members share the schedule, so it sleeps/wakes together),
        # plain backlog that inherits the freed slots under ``sleep``
        eng = ServingEngine(None, None, n_slots=8,
                            backend=StubModelBackend(),
                            agentic_sleep=(variant == "sleep"))
        rng = np.random.default_rng(5)
        n = 0
        for _ in range(4):
            eng.submit(rng.integers(1, 250, 8), 12,
                       tool_calls=((4, 6), (8, 3)))
            n += 1
        for _ in range(2):
            eng.submit(rng.integers(1, 250, 8), 12, gang="ag",
                       tool_calls=((6, 8),))
            n += 1
        for _ in range(8):
            eng.submit(rng.integers(1, 250, 8), 10)
            n += 1
        snap = _drive(eng, n)
        c = eng.counters()
        snap.update({k: c[k] for k in ("sleeps", "holds", "wakes",
                                       "wake_home", "wake_away",
                                       "wake_reprefills")})
        return snap
    if case == "agentic_paged":
        # a multi-turn session through the paged backend: both wakes find
        # the prefix KV pages resident — block-table re-points, zero pool
        # copies, no re-prefill.  The tokens of a float model depend on the
        # JAX build, so in place of a digest the snapshot pins what the
        # paged backend guarantees at float32 without ring wrap: its
        # streams equal the dense backend's on the same trace
        import jax
        from repro.configs import get_config
        from repro.models import api
        from repro.serving import PagedJaxModelBackend
        cfg = get_config("yi-6b").reduced(vocab=97)
        params = api.init(cfg, jax.random.PRNGKey(0))

        def session(backend) -> ServingEngine:
            eng = ServingEngine(cfg, params, n_slots=4, cache_len=32,
                                backend=backend)
            rng = np.random.default_rng(7)
            eng.submit(rng.integers(1, 97, 6), 10,
                       tool_calls=((3, 4), (6, 3)))
            eng.submit(rng.integers(1, 97, 5), 6)
            return eng

        pb = PagedJaxModelBackend(cfg, params, 32, page_size=8)
        eng = session(pb)
        snap = _drive(eng, 2)
        dense = session(None)               # default: JaxModelBackend
        _drive(dense, 2)
        assert _streams(eng) == _streams(dense)
        snap["streams"] = "paged == dense"
        c = eng.counters()
        snap.update({k: c[k] for k in ("sleeps", "wakes",
                                       "wake_reprefills")})
        snap["pool_copies"] = pb.stats["pool_copies"]
        snap["table_splices"] = pb.stats["table_splices"]
        assert snap["pool_copies"] == 0 and snap["wake_reprefills"] == 0
        return snap
    eng, spec, regen = build(case, variant)
    n = _submit(eng, spec)
    return _drive(eng, n, regen)


CASES = [("single_skew", "admission"), ("single_skew", "runtime"),
         ("single_churn", "runtime"),
         ("multihost_skew", "naive"), ("multihost_skew", "dcn"),
         ("hbm_pressure", "blind"), ("hbm_pressure", "aware"),
         ("dcn_rebalance", "flat"), ("dcn_rebalance", "local"),
         ("open_loop", "fifo"), ("open_loop", "sla"),
         ("agentic_tool", "hold"), ("agentic_tool", "sleep"),
         ("agentic_paged", "paged")]


# ---------------------------------------------------------------------------
# snapshots (regenerate: PYTHONPATH=src python tests/test_serving_golden.py)
# ---------------------------------------------------------------------------

GOLDEN = {
    ('single_skew', 'admission'): {'steps': 55, 'streams': 'dbb35fc690fba08b', 'steals': 0, 'steal_refusals': 0, 'rebalances': 0, 'kv_migrations': 0, 'kv_page_moves': 0, 'kv_host_moves': 0, 'kv_parks': 0, 'prefills': 21, 'hbm_slot_waits': 0, 'hbm_refusals': 0, 'stall_steps': 0.0},
    ('single_skew', 'runtime'): {'steps': 35, 'streams': 'dbb35fc690fba08b', 'steals': 6, 'steal_refusals': 0, 'rebalances': 1, 'kv_migrations': 6, 'kv_page_moves': 2, 'kv_host_moves': 0, 'kv_parks': 0, 'prefills': 21, 'hbm_slot_waits': 0, 'hbm_refusals': 0, 'stall_steps': 8.375},
    ('single_churn', 'runtime'): {'steps': 22, 'streams': 'a378043789385b15', 'steals': 0, 'steal_refusals': 0, 'rebalances': 0, 'kv_migrations': 0, 'kv_page_moves': 0, 'kv_host_moves': 0, 'kv_parks': 4, 'prefills': 16, 'hbm_slot_waits': 0, 'hbm_refusals': 0, 'stall_steps': 0.0},
    ('multihost_skew', 'naive'): {'steps': 82, 'streams': '55cfc4500c9ca06d', 'steals': 17, 'steal_refusals': 0, 'rebalances': 2, 'kv_migrations': 31, 'kv_page_moves': 18, 'kv_host_moves': 13, 'kv_parks': 0, 'prefills': 64, 'hbm_slot_waits': 0, 'hbm_refusals': 0, 'stall_steps': 809.75},
    ('multihost_skew', 'dcn'): {'steps': 65, 'streams': '55cfc4500c9ca06d', 'steals': 22, 'steal_refusals': 0, 'rebalances': 2, 'kv_migrations': 34, 'kv_page_moves': 9, 'kv_host_moves': 4, 'kv_parks': 0, 'prefills': 64, 'hbm_slot_waits': 0, 'hbm_refusals': 0, 'stall_steps': 296.625},
    ('hbm_pressure', 'blind'): {'steps': 55, 'streams': 'ed6dbeec973b4ef5', 'steals': 35, 'steal_refusals': 0, 'rebalances': 2, 'kv_migrations': 16, 'kv_page_moves': 11, 'kv_host_moves': 6, 'kv_parks': 0, 'prefills': 30, 'hbm_slot_waits': 0, 'hbm_refusals': 173, 'stall_steps': 261.25},
    ('hbm_pressure', 'aware'): {'steps': 37, 'streams': 'ed6dbeec973b4ef5', 'steals': 4, 'steal_refusals': 18, 'rebalances': 1, 'kv_migrations': 4, 'kv_page_moves': 2, 'kv_host_moves': 1, 'kv_parks': 0, 'prefills': 30, 'hbm_slot_waits': 228, 'hbm_refusals': 0, 'stall_steps': 24.75},
    ('dcn_rebalance', 'flat'): {'steps': 64, 'streams': '90b7d19ba0bb5e62', 'steals': 17, 'steal_refusals': 0, 'rebalances': 1, 'kv_migrations': 32, 'kv_page_moves': 11, 'kv_host_moves': 9, 'kv_parks': 0, 'prefills': 76, 'hbm_slot_waits': 0, 'hbm_refusals': 0, 'stall_steps': 483.125},
    ('dcn_rebalance', 'local'): {'steps': 39, 'streams': '90b7d19ba0bb5e62', 'steals': 19, 'steal_refusals': 0, 'rebalances': 1, 'kv_migrations': 36, 'kv_page_moves': 5, 'kv_host_moves': 4, 'kv_parks': 0, 'prefills': 76, 'hbm_slot_waits': 0, 'hbm_refusals': 0, 'stall_steps': 298.5},
    ('open_loop', 'fifo'): {'steps': 125, 'streams': '76c37afcead250e6', 'steals': 0, 'steal_refusals': 0, 'rebalances': 0, 'kv_migrations': 0, 'kv_page_moves': 0, 'kv_host_moves': 0, 'kv_parks': 0, 'prefills': 54, 'hbm_slot_waits': 0, 'hbm_refusals': 0, 'stall_steps': 0.0, 'preemptions': 0, 'preempt_parks': 0, 'demotions': 0},
    ('open_loop', 'sla'): {'steps': 112, 'streams': '76c37afcead250e6', 'steals': 3, 'steal_refusals': 0, 'rebalances': 2, 'kv_migrations': 6, 'kv_page_moves': 3, 'kv_host_moves': 2, 'kv_parks': 6, 'prefills': 54, 'hbm_slot_waits': 0, 'hbm_refusals': 0, 'stall_steps': 29.375, 'preemptions': 4, 'preempt_parks': 6, 'demotions': 0},
    ('agentic_tool', 'hold'): {'steps': 36, 'streams': 'db5874ed0bb3a591', 'steals': 0, 'steal_refusals': 0, 'rebalances': 0, 'kv_migrations': 0, 'kv_page_moves': 0, 'kv_host_moves': 0, 'kv_parks': 0, 'prefills': 14, 'hbm_slot_waits': 0, 'hbm_refusals': 0, 'stall_steps': 0.0, 'sleeps': 0, 'holds': 10, 'wakes': 10, 'wake_home': 0, 'wake_away': 0, 'wake_reprefills': 0},
    ('agentic_tool', 'sleep'): {'steps': 28, 'streams': 'db5874ed0bb3a591', 'steals': 2, 'steal_refusals': 0, 'rebalances': 0, 'kv_migrations': 6, 'kv_page_moves': 5, 'kv_host_moves': 0, 'kv_parks': 10, 'prefills': 14, 'hbm_slot_waits': 0, 'hbm_refusals': 0, 'stall_steps': 2.875, 'sleeps': 10, 'holds': 0, 'wakes': 10, 'wake_home': 5, 'wake_away': 5, 'wake_reprefills': 0},
    ('agentic_paged', 'paged'): {'steps': 14, 'streams': 'paged == dense', 'steals': 0, 'steal_refusals': 0, 'rebalances': 0, 'kv_migrations': 0, 'kv_page_moves': 0, 'kv_host_moves': 0, 'kv_parks': 2, 'prefills': 2, 'hbm_slot_waits': 0, 'hbm_refusals': 0, 'stall_steps': 0.0, 'sleeps': 2, 'wakes': 2, 'wake_reprefills': 0, 'pool_copies': 0, 'table_splices': 2},
}


@pytest.mark.parametrize("case,variant", CASES)
def test_serving_golden_trace(case: str, variant: str):
    got = simulate(case, variant)
    want = GOLDEN[(case, variant)]
    assert got == want, (case, variant, got, want)


@pytest.mark.parametrize("case,variant", CASES)
def test_serving_golden_trace_with_span_log_attached(case: str,
                                                     variant: str):
    """An attached span log (``repro.core.trace``) records the engine's
    phases and changes nothing it serves: every golden still holds."""
    log = trace.SpanLog()
    trace.attach(log)
    try:
        got = simulate(case, variant)
    finally:
        trace.detach()
    assert got == GOLDEN[(case, variant)], (case, variant, got)
    assert log.records and log.records[0][0] == "engine.step"


def test_mode_never_changes_streams():
    """Scheduling (steal pricing, capacity policy) must never change what
    was decoded — the digests across variants of one case are equal."""
    by_case: dict = {}
    for case, variant in CASES:
        by_case.setdefault(case, set()).add(GOLDEN[(case, variant)]["streams"])
    for case, digests in by_case.items():
        assert len(digests) == 1, (case, digests)


def generate() -> dict:
    return {(case, variant): simulate(case, variant)
            for case, variant in CASES}


def format_golden(snapshots: dict) -> str:
    lines = ["GOLDEN = {"]
    lines += [f"    {k!r}: {v!r}," for k, v in snapshots.items()]
    lines.append("}")
    return "\n".join(lines)


def check_drift(out_path=None) -> int:
    """Regenerate all snapshots; report any that differ from GOLDEN."""
    regen = generate()
    if out_path:
        with open(out_path, "w") as f:
            f.write(format_golden(regen) + "\n")
    drifted = {k: (GOLDEN.get(k), v) for k, v in regen.items()
               if GOLDEN.get(k) != v}
    missing = sorted(k for k in GOLDEN if k not in regen)
    if not drifted and not missing:
        print(f"serving golden traces stable: {len(regen)} snapshots match")
        return 0
    for k, (want, got) in sorted(drifted.items()):
        print(f"DRIFT {k}:\n  committed:   {want!r}\n  regenerated: {got!r}")
    for k in missing:
        print(f"MISSING {k}: committed but no longer generated")
    print(f"{len(drifted)} drifted, {len(missing)} missing — if intentional, "
          "regenerate with `PYTHONPATH=src python tests/test_serving_golden"
          ".py` and paste over GOLDEN")
    return 1


if __name__ == "__main__":
    import sys
    argv = sys.argv[1:]
    if "--check" in argv:
        out = None
        if "--out" in argv:
            out = argv[argv.index("--out") + 1]
        sys.exit(check_drift(out))
    print(format_golden(generate()))
