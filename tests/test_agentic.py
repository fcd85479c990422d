"""Agentic sleep/wake lifecycle tests.

The sleep/wake layer turns a tool-calling request into the paper's
sleeping thread: at a ``tool_calls`` marker the session parks its KV via
the park/splice machinery and frees its slot (``agentic_sleep``), then
wakes on the tool response — scheduled (``think_steps``) or external
(:meth:`ServingEngine.wake`) — spliced back where the wake-affinity
quote says, without re-prefill while its KV survives.

Covered here:

* lifecycle units — the slot frees on sleep and admits backlog, the HBM
  reservation is refunded (or retained under ``sleep_retain_hbm``), a
  wake splices without touching the prefill counter, stale sessions past
  ``session_ttl`` drop their KV and re-prefill on wake, external wakes
  drain ``think_steps=None`` markers;
* wake affinity — an idle fleet always restores home; genuine backlog at
  home buys the away move; ``wake_quote=False`` pins home;
* the latency-ledger regression — TTFT stays a first-admission contract
  and think gaps never leak into inter-token percentiles (the
  double-counting ``latency_summary`` would otherwise do);
* a hypothesis property — random sleep/wake/submit traffic on 1-4 pod x
  1-4 host fleets conserves every request (no loss, no resurrection) and
  decodes streams identical to a never-sleeping run, because the stub
  stream is a pure function of token history and sleeping may only move
  tokens in time.
"""

import numpy as np
import pytest

import hypothesis.strategies as st
from hypothesis import given, settings

from repro.core.bubble import reset_ids
from repro.serving import (SERVE_COST, ServingEngine, SleepingLedger,
                           StubModelBackend)
from repro.serving.engine import SleepEntry

PROMPT = np.arange(1, 7, dtype=np.int32)


def make_engine(n_slots=4, group=4, hosts=1, pods=1, **kw):
    reset_ids()
    return ServingEngine(None, None, n_slots=n_slots, group=group,
                         hosts=hosts, pods=pods, backend=StubModelBackend(),
                         cost_model=SERVE_COST, **kw)


def streams(eng):
    return {r.rid: tuple(r.out_tokens) for r in eng.completed}


def run_until(eng, pred, cap=200):
    while not pred(eng):
        eng.step()
        assert eng.steps < cap, "condition never reached"


# ---------------------------------------------------------------------------
# the ledger itself
# ---------------------------------------------------------------------------

def test_sleeping_ledger_api():
    led = SleepingLedger()
    a = SleepEntry(1, None, "kv", 7, None, slept_step=2, wake_at=5)
    b = SleepEntry(2, None, "kv", 9, None, slept_step=3, wake_at=None)
    led.add(a)
    led.add(b)
    assert len(led) == 2 and 1 in led and 3 not in led
    assert led.get(2) is b and led.get(3) is None
    assert led.due(4.0) == [] and led.due(5.0) == [a]    # external: never due
    assert led.stale(4.0, ttl=2) == [a]
    b.state = None                                       # evicted: not stale
    assert led.stale(50.0, ttl=2) == [a]
    assert led.pop(1) is a and len(led) == 1
    with pytest.raises(AssertionError):
        led.add(SleepEntry(2, None, "kv", 0, None, 0, None))


# ---------------------------------------------------------------------------
# lifecycle units
# ---------------------------------------------------------------------------

def test_sleep_frees_slot_for_backlog():
    eng = make_engine(n_slots=2, group=2)
    a = eng.submit(PROMPT, 8, tool_calls=((2, 8),))
    b = eng.submit(PROMPT, 6)
    c = eng.submit(PROMPT, 6)              # no free slot until someone yields
    run_until(eng, lambda e: e.stats.sleeps == 1)
    assert a in eng._sleeping
    assert all(r is None or r.rid != a for r in eng.slot_req)
    eng.step()                             # the freed slot admits the backlog
    resident = {r.rid for r in eng.slot_req if r is not None}
    assert c in resident
    done = eng.run()
    assert sorted(r.rid for r in done) == [a, b, c]
    assert eng.stats.wakes == eng.stats.sleeps == 1


def test_sleep_refunds_hbm_reservation():
    eng = make_engine(n_slots=2, group=2, hbm_budget=2.0, kv_bytes=1.0)
    eng.submit(PROMPT, 8, tool_calls=((2, 6),))
    run_until(eng, lambda e: e.stats.sleeps == 1)
    assert sum(eng.hbm_used) == 0.0        # sleeper's bytes refunded
    eng.run()
    assert sum(eng.hbm_used) == 0.0


def test_sleep_retain_hbm_keeps_reservation():
    eng = make_engine(n_slots=2, group=2, hbm_budget=2.0, kv_bytes=1.0,
                      sleep_retain_hbm=True)
    rid = eng.submit(PROMPT, 8, tool_calls=((2, 6),))
    run_until(eng, lambda e: e.stats.sleeps == 1)
    assert sum(eng.hbm_used) == 1.0        # held for the wake
    assert eng._sleeping.get(rid).retained is not None
    eng.run()
    assert sum(eng.hbm_used) == 0.0        # released when the entry left


def test_wake_splices_without_reprefill():
    eng = make_engine()
    rid = eng.submit(PROMPT, 8, tool_calls=((3, 4),))
    done = eng.run()
    assert len(done) == 1 and len(done[0].out_tokens) == 8
    c = eng.counters()
    assert eng.stats.prefills == 1         # the one fresh prefill, ever
    assert c["wake_reprefills"] == 0
    assert c["sleeps"] == c["wakes"] == 1
    assert c["kv_parks"] >= 1 and c["kv_splices"] >= 1
    ref = make_engine()
    assert ref.submit(PROMPT, 8) == rid
    ref.run()
    assert streams(eng) == streams(ref)    # sleeping never changes tokens


def test_stale_session_evicted_and_reprefilled():
    eng = make_engine(session_ttl=3)
    eng.submit(PROMPT, 8, tool_calls=((2, 12),))
    done = eng.run()
    c = eng.counters()
    assert c["stale_evictions"] == 1       # KV dropped past the TTL...
    assert c["wake_reprefills"] == 1       # ...so the wake rebuilt it
    assert c["wakes"] == 1
    ref = make_engine()
    ref.submit(PROMPT, 8)
    ref.run()
    assert streams(eng) == streams(ref)
    assert len(done) == 1


def test_external_wake_drains_none_marker():
    eng = make_engine()
    rid = eng.submit(PROMPT, 6, tool_calls=((2, None),))
    run_until(eng, lambda e: e.stats.sleeps == 1)
    for _ in range(5):
        eng.step()                         # nothing schedules it...
    assert not eng._drained() and rid in eng._sleeping
    assert eng.wake(rid) is True           # ...until the client delivers
    assert eng.wake(rid) is False          # not asleep twice
    done = eng.run()
    assert [r.rid for r in done] == [rid]
    assert len(done[0].out_tokens) == 6


def test_gang_sleeps_and_wakes_together():
    eng = make_engine(n_slots=4, group=4)
    calls = ((3, 5),)
    a = eng.submit(PROMPT, 8, gang="g0", tool_calls=calls)
    b = eng.submit(PROMPT, 8, gang="g0", tool_calls=calls)
    done = eng.run()
    assert len(done) == 2
    c = eng.counters()
    assert c["sleeps"] == c["wakes"] == 2
    ref = make_engine(n_slots=4, group=4)
    ref.submit(PROMPT, 8, gang="g0")
    ref.submit(PROMPT, 8, gang="g0")
    ref.run()
    assert streams(eng) == streams(ref)
    assert a != b


# ---------------------------------------------------------------------------
# wake affinity
# ---------------------------------------------------------------------------

def test_idle_fleet_wakes_home():
    eng = make_engine(n_slots=8, group=4)  # two page groups
    eng.submit(PROMPT, 8, tool_calls=((2, 6),))
    eng.run()
    c = eng.counters()
    assert c["wake_home"] == 1 and c["wake_away"] == 0


def test_home_pressure_buys_away_wake():
    eng = make_engine(n_slots=8, group=4, hbm_budget=4.0, kv_bytes=1.0)
    eng.submit(PROMPT, 12, tool_calls=((2, 6),), home="page0")
    run_until(eng, lambda e: e.stats.sleeps == 1)
    # refill home's freed budget while the session thinks: at wake time
    # the home group is at its byte budget, the sibling is idle — the
    # quote buys the away move (page-crossing toll < waiting out home)
    for _ in range(4):
        eng.submit(PROMPT, 24, home="page0")
    eng.run(max_steps=2000)
    c = eng.counters()
    assert c["wake_away"] == 1 and c["wake_home"] == 0
    assert len(eng.completed) == 5


def test_wake_quote_off_pins_home():
    eng = make_engine(n_slots=8, group=4, hbm_budget=4.0, kv_bytes=1.0,
                      wake_quote=False)
    eng.submit(PROMPT, 12, tool_calls=((2, 6),), home="page0")
    run_until(eng, lambda e: e.stats.sleeps == 1)
    for _ in range(4):
        eng.submit(PROMPT, 24, home="page0")
    eng.run(max_steps=2000)
    c = eng.counters()
    assert c["wake_home"] == 1 and c["wake_away"] == 0


# ---------------------------------------------------------------------------
# the latency-ledger regression: one request, many service intervals
# ---------------------------------------------------------------------------

def test_ttft_judged_on_first_admission_only():
    eng = make_engine()
    eng.submit(PROMPT, 8, sla="standard", tool_calls=((2, 9),))
    eng.run()
    ref = make_engine()
    ref.submit(PROMPT, 8, sla="standard")
    ref.run()
    lat = eng.latency_summary()["classes"]["standard"]
    ref_lat = ref.latency_summary()["classes"]["standard"]
    assert lat["n"] == 1                   # one TTFT sample, not one per wake
    assert lat["ttft_p99"] == ref_lat["ttft_p99"]      # first admission only
    assert lat["wakes"] == 1 and lat["wake_p99"] < 9   # wake ledger separate
    # the 9-step think gap must not leak into inter-token percentiles —
    # the double-counting this ledger would otherwise do
    assert lat["tok_p99"] <= ref_lat["tok_p99"] + 1


def test_wake_latency_counts_requeue_wait():
    eng = make_engine(n_slots=2, group=2)
    eng.submit(PROMPT, 8, tool_calls=((2, 2),))
    for _ in range(4):                     # contention: the wake must queue
        eng.submit(PROMPT, 10)
    eng.run(max_steps=2000)
    lat = eng.latency_summary()["classes"]["unclassed"]
    assert lat["wakes"] == 1
    assert lat["wake_p99"] >= 1.0          # waited for a slot after waking


# ---------------------------------------------------------------------------
# property: random sleep/wake/submit traffic conserves every request
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(pods=st.integers(min_value=1, max_value=4),
       hosts=st.integers(min_value=1, max_value=4),
       seed=st.integers(min_value=0, max_value=10 ** 6))
def test_random_traffic_conserved_and_stream_identical(pods, hosts, seed):
    rng = np.random.default_rng(seed)
    n_slots = pods * hosts * 4
    arrivals = []                          # (step, prompt, new, calls, gang)
    for i in range(int(rng.integers(3, 13))):
        new = int(rng.integers(2, 12))
        calls, at = [], 1
        while at < new and rng.random() < 0.55:
            think = None if rng.random() < 0.3 else int(rng.integers(1, 9))
            calls.append((at, think))
            at += int(rng.integers(1, 4))
        gang = f"g{i // 3}" if rng.random() < 0.3 else None
        arrivals.append((int(rng.integers(0, 10)),
                         rng.integers(1, 97, int(rng.integers(2, 8))),
                         new, tuple(calls), gang))
    arrivals.sort(key=lambda a: a[0])

    def drive_arm(strip_calls):
        eng = make_engine(n_slots=n_slots, group=2, hosts=hosts, pods=pods)
        rids, i = [], 0
        while i < len(arrivals) or not eng._drained():
            now = eng.steps
            while i < len(arrivals) and arrivals[i][0] <= now:
                step, prompt, new, calls, gang = arrivals[i]
                i += 1
                rids.append(eng.submit(
                    prompt, new, gang=gang,
                    tool_calls=() if strip_calls else calls))
            if not strip_calls:
                # deliver tool responses for externally-blocked sessions:
                # randomly while young, unconditionally past a deadline
                for e in eng._sleeping.entries():
                    if e.wake_at is None and (now > 60
                                              or rng.random() < 0.4):
                        assert eng.wake(e.rid)
            eng.step()
            assert eng.steps < 3000, "traffic did not drain"
        return eng, rids

    agentic, rids = drive_arm(strip_calls=False)
    never, ref_rids = drive_arm(strip_calls=True)
    assert rids == ref_rids                # same submission order, same ids
    got = streams(agentic)
    # conservation: every request completes exactly once — no loss on the
    # sleep path, no resurrection from the ledger
    assert sorted(got) == sorted(rids)
    assert len(agentic.completed) == len(rids)
    # sleeping moves tokens in time, never changes them
    assert got == streams(never)
