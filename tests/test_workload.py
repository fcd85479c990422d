"""Open-loop workload + SLA-tier scheduling tests (stub model, no jax).

The workload layer (``repro.serving.workload``) is the arrival side:
deterministic seeded traces (Poisson / bursty / diurnal), heavy-tailed
length mixes, SLA classes.  The engine side under test is everything PR 6
grew: the WDRR admission gate riding the covering-list walk as a task
filter, multilevel-feedback demotion, KV park/splice preemption, and the
per-request latency ledger (TTFT / inter-token gaps / goodput-under-SLA).

The load-bearing invariant throughout: scheduling — priorities, WDRR,
demotion, preemption, parking — may change *when* a token decodes, never
*what* is decoded.  Streams are asserted equal across engines and
admission orders on every property run.
"""

import math

import numpy as np
import pytest

import hypothesis.strategies as st
from hypothesis import given, settings

from repro.core.bubble import reset_ids
from repro.serving import (SLA_CLASSES, ServingEngine, StubModelBackend,
                           bursty_arrivals, diurnal_arrivals, drive,
                           goodput_under_sla, make_trace, percentile,
                           poisson_arrivals)


def make_engine(n_slots=8, **kw):
    reset_ids()
    return ServingEngine(None, None, n_slots=n_slots,
                         backend=StubModelBackend(), **kw)


def streams(eng):
    return {r.rid: tuple(r.out_tokens) for r in eng.completed}


# ---------------------------------------------------------------------------
# the workload layer itself
# ---------------------------------------------------------------------------

class TestTraces:
    def test_trace_deterministic_under_seed(self):
        a = make_trace(steps=60, rate=1.3, seed=7)
        b = make_trace(steps=60, rate=1.3, seed=7)
        assert len(a) == len(b)
        for ra, rb in zip(a, b):
            assert (ra.step, ra.sla, ra.new_tokens, ra.gang) == \
                (rb.step, rb.sla, rb.new_tokens, rb.gang)
            assert np.array_equal(ra.prompt, rb.prompt)

    def test_seeds_differ(self):
        a = make_trace(steps=60, rate=1.3, seed=0)
        b = make_trace(steps=60, rate=1.3, seed=1)
        assert [(r.step, r.sla, r.new_tokens) for r in a] != \
            [(r.step, r.sla, r.new_tokens) for r in b]

    def test_every_class_arrives_with_submit_steps(self):
        trace = make_trace(steps=120, rate=1.5, seed=0)
        classes = {r.sla for r in trace}
        assert classes == {"interactive", "standard", "batch"}
        assert all(0 <= r.step < 120 for r in trace)
        assert all(r.new_tokens >= 1 and len(r.prompt) >= 1 for r in trace)
        # batch arrives as gangs; the other tiers ride solo
        assert all((r.gang is not None) == (r.sla == "batch")
                   for r in trace)

    def test_arrival_processes_shapes(self):
        rng = np.random.default_rng(0)
        for counts in (poisson_arrivals(1.5, 64, rng),
                       bursty_arrivals(3.0, 0.2, 8, 8, 64, rng),
                       diurnal_arrivals(1.5, 1.0, 16, 64, rng)):
            assert len(counts) == 64
            assert all(isinstance(c, int) and c >= 0 for c in counts)

    def test_bursty_and_diurnal_traces_drain(self):
        for process in ("bursty", "diurnal"):
            trace = make_trace(steps=48, rate=1.2, seed=2, process=process)
            eng = drive(make_engine(sla_classes=SLA_CLASSES, preempt=True),
                        trace, max_steps=20000)
            assert len(eng.completed) == len(trace)

    def test_percentile_nearest_rank(self):
        assert percentile([], 99) == 0.0
        assert percentile([5], 50) == 5.0
        xs = list(range(1, 101))          # 1..100
        assert percentile(xs, 50) == 50.0
        assert percentile(xs, 99) == 99.0
        assert percentile(xs, 100) == 100.0


# ---------------------------------------------------------------------------
# the latency ledger
# ---------------------------------------------------------------------------

class TestLatencyLedger:
    def test_ttft_stamped_at_actual_admission(self):
        """8 same-class requests onto 4 slots: the second wave's TTFT is
        the queueing delay, stamped when prefill actually ran."""
        eng = make_engine(n_slots=4)
        for _ in range(8):
            eng.submit(np.arange(1, 7, dtype=np.int32), 4, sla="standard")
        eng.run(max_steps=100)
        ttfts = sorted(r.first_token_step - r.submit_step
                       for r in eng.completed)
        assert ttfts[:4] == [0, 0, 0, 0]
        assert all(t > 0 for t in ttfts[4:])
        summary = eng.latency_summary()
        assert summary["classes"]["standard"]["n"] == 8
        assert summary["classes"]["standard"]["ttft_p50"] == 0.0
        assert summary["classes"]["standard"]["ttft_p99"] == ttfts[-1]

    def test_inter_token_gaps_counted(self):
        eng = make_engine(n_slots=2)
        eng.submit(np.arange(1, 7, dtype=np.int32), 5, sla="interactive")
        eng.run(max_steps=50)
        gaps = eng._gaps["interactive"]
        assert len(gaps) == 4             # 5 tokens = prefill + 4 decodes
        # prefill and the first decode share an engine step (gap 0);
        # uncontended decode then yields one token per step
        assert gaps == [0, 1, 1, 1]

    def test_goodput_judged_on_contract_class(self):
        """A late interactive completion is not 'good'; batch is good on
        completion alone (no TTFT SLO)."""
        eng = make_engine(n_slots=1, group=1)
        slo = SLA_CLASSES["interactive"].ttft_slo
        eng.submit(np.arange(1, 7, dtype=np.int32), slo + 4, sla="batch")
        eng.submit(np.arange(1, 9, dtype=np.int32), 2, sla="interactive")
        eng.run(max_steps=100)
        good, total = goodput_under_sla(eng.completed)
        assert total == 2
        assert good == 1                  # interactive blew its SLO; batch ok


# ---------------------------------------------------------------------------
# WDRR admission + demotion + preemption
# ---------------------------------------------------------------------------

class TestSLAScheduling:
    def test_wdrr_keeps_batch_flowing_under_interactive_load(self):
        """Pure priorities would starve batch until the interactive queue
        empties; the deficit round-robin must admit batch work while
        interactive backlog still exists."""
        eng = make_engine(n_slots=4, sla_classes=SLA_CLASSES)
        for _ in range(12):
            eng.submit(np.arange(1, 7, dtype=np.int32), 6, sla="interactive")
        for _ in range(4):
            eng.submit(np.arange(1, 5, dtype=np.int32), 6, sla="batch")
        eng.run(max_steps=400)
        assert len(eng.completed) == 16
        first_batch = min(r.first_token_step for r in eng.completed
                          if r.sla == "batch")
        last_interactive = max(r.first_token_step for r in eng.completed
                               if r.sla == "interactive")
        assert first_batch < last_interactive, \
            "WDRR never admitted batch under interactive backlog"

    def test_priority_only_engine_starves_batch_longer(self):
        """The same load on an SLA-less engine with raw priorities admits
        every interactive request first — the contrast that proves the
        WDRR gate is doing the arbitration."""
        def first_batch_admission(sla_classes):
            eng = make_engine(n_slots=4, sla_classes=sla_classes)
            for _ in range(12):
                eng.submit(np.arange(1, 7, dtype=np.int32), 6,
                           prio=2, sla="interactive")
            for _ in range(4):
                eng.submit(np.arange(1, 5, dtype=np.int32), 6,
                           prio=0, sla="batch")
            eng.run(max_steps=400)
            return min(r.first_token_step for r in eng.completed
                       if r.sla == "batch")

        assert first_batch_admission(SLA_CLASSES) < \
            first_batch_admission(None)

    def test_long_runner_demotes_but_keeps_contract(self):
        cls = SLA_CLASSES["interactive"]
        eng = make_engine(n_slots=2, sla_classes=SLA_CLASSES)
        rid = eng.submit(np.arange(1, 7, dtype=np.int32),
                         cls.demote_after + 8, sla="interactive")
        eng.run(max_steps=200)
        req = eng._reqs[rid]
        assert eng.stats.demotions >= 1
        assert req.tier == cls.demote_to          # scheduled as standard...
        assert req.sla == "interactive"           # ...judged as interactive

    def test_preemption_parks_batch_for_interactive(self):
        """Slots full of a batch gang, an interactive arrival: the gang's
        KV parks (park/splice path), the interactive request admits, and
        the resumed gang decodes its exact continuation (streams equal to
        an unpreempted run)."""
        def run(preempt):
            eng = make_engine(n_slots=4, sla_classes=SLA_CLASSES,
                              preempt=preempt, preempt_cooldown=2)
            rng = np.random.default_rng(0)
            for _ in range(4):
                eng.submit(rng.integers(1, 200, 6), 24, sla="batch",
                           gang="bg")
            for _ in range(3):
                eng.step()
            rid = eng.submit(rng.integers(1, 200, 6), 4, sla="interactive")
            eng.run(max_steps=400)
            assert len(eng.completed) == 5
            return eng, rid

        pre, rid = run(True)
        base, _ = run(False)
        assert pre.stats.preemptions >= 1 and pre.stats.preempt_parks >= 1
        assert streams(pre) == streams(base), \
            "preemption changed a decoded stream"
        # the interactive request got in measurably earlier
        ttft = {e: next(r.first_token_step - r.submit_step
                        for r in eng.completed if r.rid == rid)
                for e, (eng, rid) in (("pre", (pre, rid)),
                                      ("base", (base, rid)))}
        assert ttft["pre"] < ttft["base"]

    def test_same_class_streams_order_invariant(self):
        """Same-class arrivals submitted in opposite per-step order decode
        identical streams (matched by prompt — rids differ)."""
        trace = [r for r in make_trace(steps=40, rate=1.5, seed=3)
                 if r.sla == "standard"]
        a = drive(make_engine(sla_classes=SLA_CLASSES), list(trace),
                  max_steps=20000)
        by_step: dict[int, list] = {}
        for r in trace:
            by_step.setdefault(r.step, []).append(r)
        flipped = [r for s in sorted(by_step) for r in reversed(by_step[s])]
        b = drive(make_engine(sla_classes=SLA_CLASSES), flipped,
                  max_steps=20000)
        sa = sorted((tuple(r.prompt), tuple(r.out_tokens))
                    for r in a.completed)
        sb = sorted((tuple(r.prompt), tuple(r.out_tokens))
                    for r in b.completed)
        assert sa == sb


# ---------------------------------------------------------------------------
# the open-loop no-starvation property (satellite 4)
# ---------------------------------------------------------------------------

class TestOpenLoopNoStarvation:
    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2 ** 16),
           rate=st.floats(min_value=0.8, max_value=2.2))
    def test_everyone_completes_no_class_unbounded(self, seed, rate):
        """Sustained Poisson load, all three SLA classes, WDRR + demotion
        + preemption on: every request completes, every class's p99 TTFT
        is bounded by the run itself, preempted batch gangs resume via
        splice with exact streams (equal to the FIFO engine's, which
        never preempts), and the ledger accounts every completion."""
        trace = make_trace(steps=48, rate=rate, seed=seed)
        if not trace:
            return
        sla = drive(make_engine(sla_classes=SLA_CLASSES, preempt=True,
                                preempt_cooldown=4),
                    trace, max_steps=40000)
        fifo = drive(make_engine(mode="admission"), trace, max_steps=40000)
        # no starvation: every arrival completed, on both engines
        assert len(sla.completed) == len(trace) == len(fifo.completed)
        # exact streams across engines — including any parked-and-resumed
        # gang (the splice path restores the precise continuation)
        assert streams(sla) == streams(fifo)
        summary = sla.latency_summary()
        for name, row in summary["classes"].items():
            assert row["ttft_p99"] < sla.steps, (name, row)
            assert row["tok_p99"] < sla.steps, (name, row)
        assert summary["goodput"]["total"] == len(trace)
        # ledger sanity: stamps are ordered and complete
        for r in sla.completed:
            assert r.first_token_step is not None
            assert r.submit_step <= r.first_token_step
            assert r.first_token_step <= r.finish_step
