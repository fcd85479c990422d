"""Scheduler trace tool tests (paper §6 'analysis tools based on tracing'),
and the span log of the serving program's phases."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import (BubblePolicy, Simulator, balanced_tree, novascale_16,
                        reset_ids, stripes_workload, trace)
from repro.core.scheduler import BubbleScheduler
from repro.core.trace import Tracer
from repro.serving import (SLA_CLASSES, ServingEngine, StubModelBackend,
                           drive, make_trace)


def test_trace_records_schedules_and_bursts():
    topo = novascale_16()
    sched = BubbleScheduler(topo)
    tracer = Tracer(sched)
    root = balanced_tree([4, 4], work=5.0)
    sched.wake_up_bubble(root)
    for cpu in range(16):
        t = sched.next_thread(cpu)
        if t is not None:
            t.remaining = 0.0
    s = tracer.summary()
    assert s.get("schedule", 0) == 16
    assert s.get("burst", 0) >= 4
    assert tracer.timeline()


def test_locality_report_on_bubble_schedule():
    """The bubbles policy must keep ≥90% of schedules data-local after the
    first (first-touch) cycle — the check the paper's tool is for."""
    topo = novascale_16()
    pol = BubblePolicy(topo)
    tracer = Tracer(pol.sched)
    root = stripes_workload(16, work=50.0, group=4)
    sim = Simulator(topo, pol, mem_fraction=0.25, contention=0.5)
    sim.run(root, cycles=4)
    rep = tracer.locality_report(topo, sim.homes, list(root.threads()))
    assert rep["total"] > 0
    assert rep["fraction"] >= 0.9, rep


def test_level_histogram_prefers_local_levels():
    topo = novascale_16()
    pol = BubblePolicy(topo)
    tracer = Tracer(pol.sched)
    root = stripes_workload(16, work=50.0, group=4)
    Simulator(topo, pol, mem_fraction=0.25).run(root, cycles=2)
    hist = tracer.level_histogram()
    # threads are released on node lists by bursting bubbles
    assert hist.get("node", 0) + hist.get("cpu", 0) > hist.get("machine", 0)


# -- the span log: the serving program's phases on the host clock ----------

ENGINE_PHASES = {"engine.schedule", "engine.prefill", "engine.splice",
                 "engine.decode", "engine.retire"}


@pytest.fixture
def log():
    lg = trace.SpanLog()
    trace.attach(lg)
    yield lg
    trace.detach()


def test_detached_span_is_one_shared_noop():
    assert trace.detach() is None
    a, b = trace.span("x"), trace.span("y", rid=3)
    assert a is b
    with a as info:
        assert info is None


def test_spans_record_parent_times_and_info(log):
    with trace.span("outer", k=1) as outer:
        with trace.span("inner") as inner:
            inner["rid"] = 7
        outer["more"] = 2
    with trace.span("next"):
        pass
    assert [r[0] for r in log.records] == ["outer", "inner", "next"]
    assert [r[3] for r in log.records] == [None, 0, None]
    assert log.records[0][4] == {"k": 1, "more": 2}
    assert log.records[1][4] == {"rid": 7}
    (_, a0, b0, _, _), (_, a1, b1, _, _), (_, a2, b2, _, _) = log.records
    assert a0 <= a1 <= b1 <= b0 <= a2 <= b2


def _sla_engine() -> tuple[ServingEngine, int]:
    """The open-loop SLA trace of the serving goldens: preemption parks
    batch gangs inside the step's schedule phase."""
    reset_ids()
    tr = make_trace(steps=48, rate=1.2, seed=3)
    eng = ServingEngine(None, None, n_slots=8, group=2, hosts=2,
                        backend=StubModelBackend(), sla_classes=SLA_CLASSES,
                        preempt=True, preempt_cooldown=4)
    drive(eng, tr)
    return eng, len(tr)


def test_engine_spans_nest_under_the_step_and_share_request_ids(log):
    eng, n = _sla_engine()
    assert len(eng.completed) == n and eng.stats.preemptions > 0
    recs = log.records
    name = [r[0] for r in recs]
    for nm, a, b, parent, info in recs:
        assert a <= b
        if parent is None:
            assert nm == "engine.step" and info["live"] >= 0
            continue
        up = name[parent]
        if nm in ENGINE_PHASES:
            assert up == "engine.step", (nm, up)
        else:
            assert nm == "engine.extract", nm
            assert up in ("engine.schedule", "engine.retire"), up
            assert name[recs[parent][3]] == "engine.step"
    assert name.count("engine.step") == eng.steps

    def rids(nm, key="rids"):
        return {r for x in recs if x[0] == nm for r in x[4].get(key, ())}

    for req in eng.completed:
        assert req.rid in rids("engine.schedule")        # claimed
        assert req.rid in rids("engine.prefill")
        assert req.rid in rids("engine.splice")
        assert req.rid in rids("engine.retire")          # finished
        if req.max_new_tokens > 1:
            assert req.rid in rids("engine.decode")
    parked = rids("engine.schedule", "parked")
    extracted = {x[4]["rid"] for x in recs if x[0] == "engine.extract"}
    assert parked and parked <= extracted
    # a parked request comes back through a splice, not a second prefill
    assert parked <= rids("engine.splice")
    steals = sum(x[4]["steals"] for x in recs if x[0] == "engine.schedule")
    assert steals == eng.sched.stats.steals


def test_detached_log_records_nothing_and_changes_nothing():
    lg = trace.SpanLog()
    detached, n = _sla_engine()
    trace.attach(lg)
    try:
        attached, _ = _sla_engine()
    finally:
        trace.detach()
    quiet = trace.SpanLog()
    again, _ = _sla_engine()
    assert lg.records and not quiet.records

    def outcome(eng):
        return (eng.steps, sorted((r.rid, tuple(r.out_tokens))
                                  for r in eng.completed), eng.counters())

    assert outcome(detached) == outcome(attached) == outcome(again)


def test_paged_backend_steps_nest_in_the_engine_phases():
    import jax
    from repro.configs import get_config
    from repro.models import api
    from repro.serving import PagedJaxModelBackend
    cfg = get_config("yi-6b").reduced(vocab=97)
    params = api.init(cfg, jax.random.PRNGKey(0))
    pb = PagedJaxModelBackend(cfg, params, 32, page_size=8)
    eng = ServingEngine(cfg, params, n_slots=4, cache_len=32, backend=pb)
    rng = np.random.default_rng(1)
    for length in (5, 12, 12):
        eng.submit(rng.integers(1, 97, length), 4)
    lg = trace.SpanLog()                       # with profiler annotations
    trace.attach(lg)
    try:
        eng.run(max_steps=50)
    finally:
        trace.detach()
    assert len(eng.completed) == 3
    name = [r[0] for r in lg.records]
    under = {}
    for nm, _, _, parent, info in lg.records:
        if parent is not None:
            under.setdefault(nm, set()).add(name[parent])
    for step in ("forward", "readback", "handles"):
        assert under["prefill." + step] == {"engine.prefill"}
    for step in ("prep", "launch", "readback"):
        assert under["decode." + step] == {"engine.decode"}
    assert under["splice.page_in"] == {"engine.splice"}
    waves = [r[4] for r in lg.records if r[0] == "engine.prefill"]
    assert sorted((w["n"], w["length"]) for w in waves) == [(1, 5), (2, 12)]
    # pages written: a 5-token prompt fills one 8-token page, two
    # 12-token prompts two each
    pages = sorted(r[4]["pages"] for r in lg.records
                   if r[0] == "splice.page_in")
    assert sum(pages) == pb.stats["pool_page_writes"] == 5


def test_the_served_decode_program_is_named_paged_decode():
    """The trace's readers find the decode's device time by its program
    name, ``jit_paged_decode``; a rename or a wrapping jit would leave
    ``decode_program_ms`` empty."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import api
    from repro.serving import PagedJaxModelBackend
    cfg = get_config("yi-6b").reduced(vocab=97)
    params = api.init(cfg, jax.random.PRNGKey(0))
    pb = PagedJaxModelBackend(cfg, params, 32, page_size=8)
    shard, tokens = pb.init(4)
    text = pb._decode.lower(
        pb.params, jnp.asarray(tokens), shard.states,
        jnp.asarray(shard.table), jnp.asarray(shard.lengths)).as_text()
    assert text.startswith("module @jit_paged_decode")


@pytest.mark.parametrize("paged", [True, False])
def test_the_served_prefill_program_is_named_prefill(paged):
    """Both backends serve the prefill as one jitted program,
    ``jit_prefill``, so its device time can be found by name."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import api
    from repro.serving import JaxModelBackend, PagedJaxModelBackend
    cfg = get_config("yi-6b").reduced(vocab=97)
    params = api.init(cfg, jax.random.PRNGKey(0))
    backend = (PagedJaxModelBackend(cfg, params, 32, page_size=8) if paged
               else JaxModelBackend(cfg, params, 32))
    text = backend._prefill.lower(
        backend.params, {"tokens": jnp.ones((2, 8), jnp.int32)}).as_text()
    assert text.startswith("module @jit_prefill")


CHILD = """
import json, jax, jax.numpy as jnp
from repro.core import trace
jax.config.update("jax_compilation_cache_dir", {cache!r})
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
x = jnp.arange(5.0)
f = jax.jit(lambda v: v * 3 + 1)
log = trace.SpanLog()
trace.attach(log)
with trace.span("outer"):
    with trace.span("first"):
        f(x).block_until_ready()
    f(x).block_until_ready()            # held in memory: no event
jax.clear_caches()
with trace.span("again"):
    f(x).block_until_ready()            # read back from the cache
trace.detach()
print(json.dumps([[r[0], r[4]] for r in log.records]))
"""


def test_compiles_and_cache_loads_are_counted_on_the_innermost_span(
        tmp_path):
    """In a child: the compile cache is process-wide JAX config."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    r = subprocess.run([sys.executable, "-c",
                        CHILD.format(cache=str(tmp_path))],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    got = dict(json.loads(r.stdout.strip().splitlines()[-1]))
    assert got["first"] == {"compiles": 1}
    assert got["outer"] == {}
    assert got["again"] == {"compiles": 0, "cache_loads": 1}
