"""Scheduler tracing — the analysis tool the paper names as future work.

    "It will then be useful to develop analysis tools based on tracing the
    scheduler at runtime, so as to check and refine scheduling strategies."
    (paper §6)

:class:`Tracer` hooks a :class:`BubbleScheduler` (monkeypatch-free: the
scheduler calls are wrapped) and records an event stream — schedules,
bursts, sinks, steals, regenerations — with timestamps and queue levels.
``timeline()`` renders a per-cpu ASCII gantt; ``locality_report()``
aggregates where each bubble's threads actually ran versus where their
data lives (the check the paper wants: did the strategy keep affinity?).
The Tracer's clock is the scheduler's logical one, not a time source.

:class:`SpanLog` is the time source: the serving program's phases
(``engine.step`` and the schedule, prefill, splice, decode, retire and
extract phases inside it, and the paged backend's own steps) as spans on
the host clock.  Nothing is recorded until a log is attached
(:func:`attach`); detached, :func:`span` hands back one shared no-op
context and reads no clock.  Attached, each span is also a profiler
annotation ``repro.<name>``, which puts it on the device trace's clock
when a profile is being captured.
"""

from __future__ import annotations

import dataclasses
import time
from collections import defaultdict
from typing import Callable, Optional

from .bubble import Bubble, Thread
from .scheduler import BubbleScheduler


@dataclasses.dataclass
class Event:
    t: float
    cpu: int
    kind: str          # schedule | burst | sink | steal | rebalance | regenerate
    task: str
    level: Optional[str] = None
    distance: Optional[int] = None   # steal: levels crossed to the victim
    cost: float = 0.0                # steal/rebalance: penalty billed (quanta)


class Tracer:
    def __init__(self, sched: BubbleScheduler):
        self.sched = sched
        self.events: list[Event] = []
        self._wrap()

    def _wrap(self) -> None:
        sched = self.sched
        orig_next = sched.next_thread
        orig_burst = sched._burst
        orig_regen = sched.regenerate
        orig_rebalance = sched.rebalance
        tracer = self

        def next_thread(cpu, now=0.0, allow_steal=True, task_filter=None):
            steals0 = sched.stats.steals
            sinks0 = sched.stats.sinks
            t = orig_next(cpu, now, allow_steal, task_filter=task_filter)
            if sched.stats.steals > steals0:
                # the scheduler remembers its latest (victim queue, loot)
                vq, loot = sched.last_steal or (None, None)
                tracer.events.append(Event(
                    now, cpu, "steal",
                    loot.name if loot is not None else "?",
                    vq.level if vq is not None else None,
                    distance=sched.stats.last_steal_distance,
                    cost=sched.stats.last_steal_cost))
            if sched.stats.sinks > sinks0:
                lq = sched.last_queue
                tracer.events.append(Event(
                    now, cpu, "sink", "?",
                    lq.level if lq is not None else None))
            if t is not None:
                lq = sched.last_queue
                # `is not None`: an emptied RunQueue is falsy (__len__)
                tracer.events.append(Event(
                    now, cpu, "schedule", t.name,
                    lq.level if lq is not None else None))
            return t

        def _burst(b, q, now):
            tracer.events.append(Event(now, -1, "burst", b.name, q.level))
            return orig_burst(b, q, now)

        def regenerate(b, running):
            tracer.events.append(Event(0.0, -1, "regenerate", b.name))
            return orig_regen(b, running)

        def rebalance(cpu, now=0.0, level=None):
            moves = orig_rebalance(cpu, now, level)
            tracer.events.append(Event(
                now, cpu, "rebalance", f"moves={moves}", level,
                cost=sched.stats.last_rebalance_cost))
            return moves

        sched.next_thread = next_thread          # type: ignore
        sched._burst = _burst                    # type: ignore
        sched.regenerate = regenerate            # type: ignore
        sched.rebalance = rebalance              # type: ignore

    # -- reports --------------------------------------------------------------
    def schedules(self) -> list[Event]:
        return [e for e in self.events if e.kind == "schedule"]

    def steals(self) -> list[Event]:
        """Steal events: ``task`` names the loot, ``level`` the victim
        queue's hierarchy level — the audit trail for the affinity
        invariant (stolen bubbles should come from the nearest level that
        had any)."""
        return [e for e in self.events if e.kind == "steal"]

    def rebalances(self) -> list[Event]:
        """Proactive-rebalance events: ``task`` carries the move count,
        ``cost`` the bulk penalty billed to the triggering cpu."""
        return [e for e in self.events if e.kind == "rebalance"]

    def steals_by_level(self) -> dict[str, int]:
        """Steal counts per victim-queue level — the per-level view of
        steal traffic that ``SchedStats`` only totals.  Mostly-local
        levels mean the affinity invariant is holding; a fat tail at
        outer levels is the steal-thrash signature the adaptive policy's
        window watches for."""
        hist: dict[str, int] = defaultdict(int)
        for e in self.steals():
            hist[e.level or "?"] += 1
        return dict(hist)

    def steal_cost_paid(self) -> float:
        """Total steal + rebalance penalty recorded in the event stream."""
        return sum(e.cost for e in self.events
                   if e.kind in ("steal", "rebalance"))

    def timeline(self, width: int = 64) -> str:
        """Per-cpu lane of scheduled task initials over event order."""
        lanes: dict[int, list[str]] = defaultdict(list)
        for e in self.schedules():
            lanes[e.cpu].append(e.task[-1] if e.task else "?")
        out = []
        for cpu in sorted(lanes):
            lane = "".join(lanes[cpu])[:width]
            out.append(f"cpu{cpu:<3d} |{lane}")
        return "\n".join(out)

    def level_histogram(self) -> dict[str, int]:
        """At which hierarchy level did threads get picked up?  A healthy
        bubble schedule picks mostly from local levels."""
        hist: dict[str, int] = defaultdict(int)
        for e in self.schedules():
            hist[e.level or "?"] += 1
        return dict(hist)

    def locality_report(self, topo, homes: dict[str, int],
                        threads: list[Thread]) -> dict:
        """Fraction of schedules that ran a thread on its data's home
        component, per level."""
        by_thread = {t.name: t for t in threads}
        local = total = 0
        for e in self.schedules():
            t = by_thread.get(e.task)
            if t is None or t.data is None or t.data not in homes:
                continue
            total += 1
            if topo.distance_factor(e.cpu, homes[t.data]) == 1.0:
                local += 1
        return {"local": local, "total": total,
                "fraction": local / total if total else None}

    def summary(self) -> dict:
        kinds: dict[str, int] = defaultdict(int)
        for e in self.events:
            kinds[e.kind] += 1
        return dict(kinds)


# ---------------------------------------------------------------------------
# spans of the serving program's phases
# ---------------------------------------------------------------------------

class SpanLog:
    """The program's spans, kept in memory until the caller writes them.

    ``records`` holds one ``(name, t0, t1, parent, info)`` per span, in
    the order the spans opened: ``t0``/``t1`` on ``clock`` (seconds),
    ``parent`` the index in ``records`` of the enclosing span (None at the
    root), ``info`` the dict the span's ``with`` binds, which the code
    inside fills (request ids as ``rid``/``rids``, counts).  A compile or
    a persistent-cache load that happens inside a span is counted on the
    innermost open one, as ``info["compiles"]`` (programs XLA compiled)
    and ``info["cache_loads"]`` (programs read back from the cache).

    Each span is also a ``jax.profiler.TraceAnnotation("repro." + name)``
    (no arguments: ``info`` stays in memory), so that a profile being
    captured holds the spans on the device's clock; with no profile
    being captured the annotation records nothing.  Spans are opened and
    closed by one thread, the engine's.
    """

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        import jax
        self.clock = clock
        self.records: list[tuple] = []
        self._open: list[int] = []        # indices of the open spans
        self._annotation = jax.profiler.TraceAnnotation

    def _count(self, key: str, n: int) -> None:
        if self._open:
            info = self.records[self._open[-1]][4]
            info[key] = info.get(key, 0) + n


class _Span:
    __slots__ = ("log", "name", "info", "i", "t0", "ann")

    def __init__(self, log: SpanLog, name: str, info: dict):
        self.log, self.name, self.info = log, name, info

    def __enter__(self) -> dict:
        log = self.log
        self.i = len(log.records)
        log.records.append((self.name, None, None,
                            log._open[-1] if log._open else None,
                            self.info))
        log._open.append(self.i)
        self.ann = log._annotation("repro." + self.name)
        self.ann.__enter__()
        self.t0 = log.clock()
        return self.info

    def __exit__(self, *exc) -> bool:
        log = self.log
        t1 = log.clock()
        self.ann.__exit__(*exc)
        log._open.pop()
        name, _, _, parent, info = log.records[self.i]
        log.records[self.i] = (name, self.t0, t1, parent, info)
        return False


class _NoSpan:
    """What :func:`span` returns with no log attached: binds None."""
    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> bool:
        return False


_NO_SPAN = _NoSpan()
_log: Optional[SpanLog] = None
_listening = False

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def span(name: str, **info):
    """A span of the attached log around a ``with`` block, which binds
    its ``info`` dict; with no log attached, the shared no-op context,
    which binds None (callers fill ``info`` only when it is not None)."""
    log = _log
    if log is None:
        return _NO_SPAN
    return _Span(log, name, info)


def attach(log: SpanLog) -> None:
    """Record the program's spans into ``log`` from now on."""
    global _log
    _listen()
    _log = log


def detach() -> Optional[SpanLog]:
    """Stop recording; returns the log that was attached."""
    global _log
    log, _log = _log, None
    return log


def _listen() -> None:
    """Count compiles and cache loads on the innermost open span: one
    pair of ``jax.monitoring`` listeners per process, idle while no log
    is attached.  JAX reports a compile event for every program it
    builds, also one it reads back from the persistent cache, and a
    cache hit inside that event; a hit is therefore a load, not a
    compile."""
    global _listening
    if _listening:
        return
    from jax import monitoring

    def on_event(event, **kw):
        if event == CACHE_HIT_EVENT and _log is not None:
            _log._count("cache_loads", 1)
            _log._count("compiles", -1)

    def on_duration(event, secs, **kw):
        if event == COMPILE_EVENT and _log is not None:
            _log._count("compiles", 1)

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)
    _listening = True
