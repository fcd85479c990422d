"""Serving-engine tests on the stub model backend — no jax, no jit.

The engine is the second client of the shared SchedulerRuntime (the
discrete simulator is the first): decode slots are the runtime's cpus, KV
page groups are the hierarchy's affinity level, a gang's KV state is its
data object.  These tests drive the whole scheduler stack (gang
co-scheduling, SLA priority ordering, steal-driven admission, next-touch
KV re-homing, queue-depth-triggered rebalance, regeneration) against the
deterministic :class:`StubModelBackend`, whose output is a hash of each
request's full token history — any KV mishandling (lost splice, stale
slot, wrong-slot write) changes the stream and fails an equality assert.
"""

import numpy as np
import pytest

import hypothesis.strategies as st
from hypothesis import given, settings

from repro.core.scheduler import StealCostModel
from repro.serving import (SERVE_COST, ServingEngine, StubModelBackend,
                           slots_topology)


def make_engine(n_slots=8, mode="runtime", **kw):
    return ServingEngine(None, None, n_slots=n_slots,
                         backend=StubModelBackend(), mode=mode, **kw)


def submit_all(eng, spec, seed=0, new_tokens=10, prompt_len=8):
    """spec: list of (gang, count, prio); returns submitted count."""
    rng = np.random.default_rng(seed)
    n = 0
    for gang, count, prio in spec:
        for _ in range(count):
            eng.submit(rng.integers(1, 200, prompt_len), new_tokens,
                       prio=prio, gang=gang)
            n += 1
    return n


def streams(eng):
    return {r.rid: tuple(r.out_tokens) for r in eng.completed}


# ---------------------------------------------------------------------------
# slots_topology: every slot is schedulable, whatever the remainder
# ---------------------------------------------------------------------------

class TestSlotsTopology:
    @settings(max_examples=40)
    @given(n_slots=st.integers(min_value=1, max_value=32),
           group=st.integers(min_value=1, max_value=8))
    def test_every_slot_is_a_leaf(self, n_slots, group):
        """The old ``n_slots // group`` derivation dropped the remainder
        (9 slots, group 4 -> 8 leaves; slot 8 unschedulable forever)."""
        topo = slots_topology(n_slots, group)
        assert topo.n_cpus == n_slots
        sizes = [len(p.children) for p in topo.components("page")]
        assert sum(sizes) == n_slots
        assert max(sizes) - min(sizes) <= 1      # remainder spread evenly
        assert min(sizes) >= 1                   # no empty page group

    def test_divisible_layout_unchanged(self):
        topo = slots_topology(8, 4)
        assert [len(p.children) for p in topo.components("page")] == [4, 4]

    def test_nine_by_four_regression(self):
        topo = slots_topology(9, 4)
        assert topo.n_cpus == 9
        # an engine over 9 slots must actually decode in all 9
        eng = make_engine(n_slots=9)
        n = submit_all(eng, [(None, 12, 0)], new_tokens=4)
        eng.run(max_steps=200)
        assert len(eng.completed) == n
        # with 12 requests of 4 tokens on 9 slots, the run needs only two
        # admission waves if every slot admits; a dropped slot forces a
        # third wave and noticeably more steps
        assert eng.steps <= 10, eng.steps


# ---------------------------------------------------------------------------
# gang co-scheduling + SLA priorities
# ---------------------------------------------------------------------------

class TestGangsAndPriorities:
    def test_gang_members_coscheduled_same_page(self):
        """A page-burst gang's first wave lands inside one page group —
        the shared-prefix KV affinity."""
        eng = make_engine(n_slots=8)
        submit_all(eng, [("g", 4, 0)])
        eng.step()
        slots = [s for s, r in enumerate(eng.slot_req) if r is not None]
        assert len(slots) == 4
        pages = {eng.topo.cpus[s].parent.index for s in slots}
        assert len(pages) == 1

    def test_sla_priority_orders_completions(self):
        """Higher-priority requests finish first when slots are scarce."""
        eng = make_engine(n_slots=4)
        submit_all(eng, [(None, 4, 0), (None, 4, 2)], new_tokens=6)
        eng.run(max_steps=200)
        prios = [r.prio for r in eng.completed]
        assert prios[:4] == [2, 2, 2, 2]
        assert prios[4:] == [0, 0, 0, 0]

    def test_late_submit_to_expanded_gang_is_scheduled(self):
        """Regression: a rebalance can *expand* a regenerated (closed,
        over-wide) gang bubble, dealing its members out individually and
        leaving the bubble object on no queue.  A later submit to that
        gang saw it 'scheduled' (members queued), inserted the new thread
        into the off-queue bubble, and nothing ever burst it — the
        request silently never decoded."""
        eng = make_engine(n_slots=8)
        n = submit_all(eng, [("fat", 16, 0), ("a", 2, 2)], new_tokens=12)
        for _ in range(3):
            eng.step()
        assert eng.regenerate_gang("fat") > 0     # closed 16-wide bubble
        guard = 0
        while eng.stats.rebalances == 0 and guard < 200:
            eng.step()
            guard += 1
        assert eng.stats.rebalances > 0, "rebalance never expanded the gang"
        rid = eng.submit(np.arange(1, 9, dtype=np.int32), 4, gang="fat")
        eng.run(max_steps=2000)
        assert sorted(r.rid for r in eng.completed) == list(range(n + 1))
        assert rid in {r.rid for r in eng.completed}

    def test_resubmit_to_finished_gang_is_scheduled(self):
        """Regression: the old sticky ``_woken`` flag meant a gang that
        completed (bubble dropped from the queues) could never be woken
        again — later submits to the same gang name were lost."""
        eng = make_engine(n_slots=4)
        submit_all(eng, [("g", 2, 0)], new_tokens=4)
        eng.run(max_steps=100)
        assert len(eng.completed) == 2
        submit_all(eng, [("g", 2, 1)], new_tokens=4, seed=1)
        eng.run(max_steps=100)
        assert len(eng.completed) == 4

    def test_admit_skips_husks_same_step(self):
        """Regression: a stale thread at the head of the queue (a
        finished gang's husk — ``remaining == 0`` / ``request.done``) made
        ``_admit`` release it and bail, idling the slot a whole engine
        step even with live work queued right behind.  The acquire loop
        must drop any number of husks and still admit the live request in
        the SAME wave.  One slot, so no other slot can mask the bug."""
        eng = make_engine(n_slots=1, group=1)
        rids = [eng.submit(np.arange(1, 9, dtype=np.int32), 4)
                for _ in range(3)]
        # forge husks: the two queue-head requests died before admission
        for q in eng.sched.queues.queues.values():
            for t in q.tasks:
                if t.request.rid in rids[:2]:
                    t.remaining = 0.0
                    t.request.done = True
        eng.step()
        assert eng.slot_req[0] is not None, "slot idled on a husk"
        assert eng.slot_req[0].rid == rids[2]
        # and the husks are gone, not wedged on a queue forever
        eng.run(max_steps=50)
        assert eng._drained()

    def test_late_joiner_honors_home(self):
        """Regression: ``submit(home=...)`` for a late joiner to an
        already-burst gang silently dropped ``home`` — the thread landed
        on the gang's burst list even when the caller routed it to
        another shard.  The caller's ``home`` must win."""
        eng = make_engine(n_slots=16, hosts=2)
        submit_all(eng, [("g", 4, 0)], new_tokens=12)
        eng.step()                      # the gang bursts on host0's side
        g = eng._gangs["gang:g"]
        assert g.burst, "precondition: gang must have burst"
        rid = eng.submit(np.arange(1, 9, dtype=np.int32), 12, gang="g",
                         home="host1")
        host1_q = eng._home_queue("host1")
        assert any(getattr(t, "request", None) is not None
                   and t.request.rid == rid for t in host1_q.tasks), \
            "late joiner's home was dropped"
        eng.run(max_steps=500)
        assert sorted(r.rid for r in eng.completed) == list(range(5))


# ---------------------------------------------------------------------------
# steal-driven admission
# ---------------------------------------------------------------------------

SKEW = [("fat", 16, 0), ("a", 2, 2), (None, 2, 1)]


class TestStealAdmission:
    def test_starving_slots_steal_from_loaded_page(self):
        eng = make_engine(mode="runtime")
        n = submit_all(eng, SKEW)
        eng.run(max_steps=1000)
        assert len(eng.completed) == n
        s = eng.sched.stats
        assert s.steals > 0
        assert eng.runtime.data_migrations > 0     # next-touch re-homed KV

    def test_runtime_beats_admission_only(self):
        """The tentpole acceptance behaviour at test scale: same request
        set, measurably fewer engine steps."""
        a = make_engine(mode="admission")
        n = submit_all(a, SKEW)
        a.run(max_steps=1000)
        b = make_engine(mode="runtime")
        submit_all(b, SKEW)
        b.run(max_steps=1000)
        assert len(a.completed) == len(b.completed) == n
        assert b.steps * 1.2 <= a.steps
        # and scheduling never changes what was decoded
        assert streams(a) == streams(b)

    def test_admission_mode_never_steals(self):
        eng = make_engine(mode="admission")
        submit_all(eng, SKEW)
        eng.run(max_steps=1000)
        assert eng.sched.stats.steals == 0
        assert eng.runtime.data_migrations == 0

    def test_steal_cost_billed_as_admission_latency(self):
        eng = make_engine(mode="runtime")
        submit_all(eng, SKEW)
        eng.run(max_steps=1000)
        assert eng.stats.stall_steps > 0
        assert eng.stats.stall_steps == pytest.approx(
            eng.sched.stats.steal_cost + eng.sched.stats.rebalance_cost)


# ---------------------------------------------------------------------------
# KV next-touch re-homing (park + batched splice)
# ---------------------------------------------------------------------------

class TestKVNextTouch:
    def test_regenerate_then_resubmit_resumes_continuation(self):
        """Regression for the stale-slot bug: the old engine popped the
        thread into an unused local, left the freed slot's token behind,
        and re-prefilled on re-admission — the resumed gang decoded from
        stale state.  Parked KV + the batched splice must make an
        interrupted run's streams identical to an uninterrupted one."""
        def run(interrupt):
            eng = make_engine(n_slots=8)
            n = submit_all(eng, [("g", 4, 0), (None, 2, 1)], new_tokens=12)
            if interrupt:
                for _ in range(4):
                    eng.step()
                assert eng.regenerate_gang("g") > 0
            eng.run(max_steps=500)
            assert len(eng.completed) == n
            return streams(eng), eng

        base, _ = run(False)
        intr, eng = run(True)
        assert base == intr
        assert eng.stats.kv_parks > 0
        assert eng.stats.prefills == 6      # no request prefilled twice

    def test_freed_slot_does_not_decode_stale_token(self):
        eng = make_engine(n_slots=4)
        submit_all(eng, [("g", 4, 0)], new_tokens=8)
        for _ in range(3):
            eng.step()
        eng.regenerate_gang("g")
        assert all(int(t) == 0 for t in eng.tokens.ravel())

    def test_migrated_gang_rehomes_kv_across_pages(self):
        """A gang stolen across page groups re-homes its KV on the first
        post-migration admission: data_migrations fires and at least one
        re-home crosses page groups."""
        eng = make_engine(mode="runtime")
        n = submit_all(eng, SKEW)
        eng.run(max_steps=1000)
        assert len(eng.completed) == n
        assert eng.stats.kv_migrations == eng.runtime.data_migrations > 0
        assert eng.stats.kv_page_moves > 0

    def test_splices_are_batched(self):
        """One splice op per admission wave, not one per request."""
        eng = make_engine(n_slots=8)
        submit_all(eng, [(None, 8, 0)])
        eng.step()
        assert eng.stats.kv_spliced_slots == 8
        assert eng.stats.kv_splices == 1

    def test_regenerate_while_member_pending_does_not_duplicate(self):
        """A gang member claimed by a steal but still waiting out its
        admission stall (``_pending``) must fold back into the regenerated
        bubble — leaving it pending too would schedule it twice."""
        eng = make_engine(mode="runtime")
        n = submit_all(eng, SKEW)
        guard = 0
        while not eng._pending and guard < 200:
            eng.step()
            guard += 1
        assert eng._pending, "workload never produced a pending admission"
        gangs = {t.parent.name for t in eng._pending.values()
                 if t.parent is not None}
        assert "gang:fat" in gangs
        eng.regenerate_gang("fat")
        assert not any(t.parent is not None and t.parent.name == "gang:fat"
                       for t in eng._pending.values())
        eng.run(max_steps=2000)
        rids = sorted(r.rid for r in eng.completed)
        assert rids == list(range(n))            # all, exactly once
        # and the interruption never changed what was decoded
        ref = make_engine(mode="admission")
        submit_all(ref, SKEW)
        ref.run(max_steps=2000)
        assert streams(ref) == streams(eng)


# ---------------------------------------------------------------------------
# wave-batched prefill: one model call per (host, length) per wave
# ---------------------------------------------------------------------------

class TestWavePrefill:
    def test_one_call_per_wave_not_per_request(self):
        """8 same-length prompts admitted in one wave prefill in ONE
        backend call; the per-request ledger still counts all 8."""
        eng = make_engine(n_slots=8)
        submit_all(eng, [(None, 8, 0)])
        eng.step()
        assert eng.stats.prefills == 8        # requests prefilled
        assert eng.stats.prefill_waves == 1   # backend calls issued

    def test_mixed_lengths_split_waves(self):
        """A wave mixes prompt lengths: one call per distinct length (the
        backend stacks same-shape prompts only)."""
        eng = make_engine(n_slots=8)
        rng = np.random.default_rng(0)
        for i in range(8):
            eng.submit(rng.integers(1, 200, 6 + (i % 2)), 4)
        eng.step()
        assert eng.stats.prefills == 8
        assert eng.stats.prefill_waves == 2

    def test_wave_prefill_streams_equal_per_request_loop(self):
        """Batching the prefill must never change a stream or a step."""
        spec = [("g", 4, 0), (None, 3, 1), ("h", 2, 2)]

        def run(wave):
            eng = make_engine(n_slots=8, wave_prefill=wave)
            n = submit_all(eng, spec, new_tokens=8)
            eng.run(max_steps=500)
            assert len(eng.completed) == n
            return eng.steps, streams(eng), eng

        steps_w, st_w, eng_w = run(True)
        steps_l, st_l, eng_l = run(False)
        assert (steps_w, st_w) == (steps_l, st_l)
        assert eng_w.stats.prefills == eng_l.stats.prefills == 9
        assert eng_w.stats.prefill_waves < eng_w.stats.prefills
        assert eng_l.stats.prefill_waves == 0    # loop mode: no wave calls

    def test_stub_wave_matches_scalar_prefill(self):
        """The vectorised stub fold is exact, not approximately equal."""
        backend = StubModelBackend()
        rng = np.random.default_rng(7)
        prompts = [rng.integers(1, 250, 11) for _ in range(6)]
        wave = backend.prefill_wave(prompts)
        for prompt, (tok, state) in zip(prompts, wave):
            stok, sstate = backend.prefill(prompt)
            assert tok == stok
            assert (state == sstate).all()


# ---------------------------------------------------------------------------
# queue-depth-triggered rebalance
# ---------------------------------------------------------------------------

class TestQueueDepthRebalance:
    def test_depth_skew_triggers_rebalance(self):
        eng = make_engine(mode="runtime")
        n = submit_all(eng, SKEW)
        eng.run(max_steps=1000)
        assert len(eng.completed) == n
        assert eng.stats.rebalances > 0
        assert eng.sched.stats.rebalance_moves > 0

    def test_zero_cost_model_never_rebalances(self):
        """The cost-benefit gate: free stealing means a re-spread can
        never pay for itself (same degradation as AdaptivePolicy under
        ZERO_COST)."""
        eng = make_engine(mode="runtime", cost_model=StealCostModel())
        n = submit_all(eng, SKEW)
        eng.run(max_steps=1000)
        assert len(eng.completed) == n
        assert eng.stats.rebalances == 0
        assert eng.sched.stats.steals > 0       # still stealing, for free

    def test_rebalance_disabled_in_admission_mode(self):
        eng = make_engine(mode="admission")
        submit_all(eng, SKEW)
        eng.run(max_steps=1000)
        assert eng.stats.rebalances == 0


# ---------------------------------------------------------------------------
# conservation: whatever the scheduling traffic, every request completes
# exactly once with exactly the asked-for tokens
# ---------------------------------------------------------------------------

class TestConservation:
    @settings(max_examples=10)
    @given(seed=st.integers(min_value=0, max_value=10_000))
    def test_random_workloads_complete_exactly(self, seed):
        rng = np.random.default_rng(seed)
        eng = make_engine(n_slots=int(rng.integers(2, 12)))
        spec = []
        for g in range(int(rng.integers(1, 5))):
            spec.append((f"g{g}" if rng.random() < 0.7 else None,
                         int(rng.integers(1, 7)), int(rng.integers(0, 3))))
        n = submit_all(eng, spec, seed=seed,
                       new_tokens=int(rng.integers(2, 9)))
        eng.run(max_steps=4000)
        rids = sorted(r.rid for r in eng.completed)
        assert rids == list(range(n))            # all, exactly once
        for r in eng.completed:
            assert len(r.out_tokens) == r.max_new_tokens
