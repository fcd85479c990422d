"""The program's own spans, read against the device trace.

The program records its phases in a ``repro.core.trace.SpanLog``:
``engine.step`` and the ``engine.*`` phases inside it (schedule, prefill,
splice, decode, retire, extract), and the paged backend's own steps
(``prefill.*``, ``decode.*``, ``splice.page_in``).  A record is
``(name, t0, t1, parent, info)`` on the host clock; with a profile being
captured each span is also a ``repro.<name>`` host event on the trace's
clock.  The jitted decode is named ``paged_decode``, so each of its
executions is a ``jit_paged_decode`` program on the TPU's module line.

This module reads both:

* ``load``: the ``repro.*`` host events and the TPU's program executions
  of a profile (``bench.trace.load`` keeps the device operations and the
  benchmark's own ``bench.*`` spans);
* readers of the log's records (host clock, the whole window) and of the
  profile (its traced part);
* ``python3 bench/program_spans.py <the arguments of bench/run.py>``: one
  run of a cell as ``bench/run.py`` makes it, with a span log attached
  after the warm-up (its profiler annotations land in the profile under
  ``--trace 1``, and record nothing otherwise).  After the run's own
  result line it prints one more, ``{"program_spans": {...}}``: the
  readings below, and the spans of every window step that took over a
  second (the stall finder).  The log rides on ``harness.main``'s
  after-warm-up hook.
"""

from __future__ import annotations

import bisect
import json
import shutil
import sys
from pathlib import Path

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace  # noqa: E402

MODULES_LINE = "XLA Modules"
DECODE_PROGRAM = "jit_paged_decode"
SLOW_STEP_S = 1.0


# ---------------------------------------------------------------------------
# the profile
# ---------------------------------------------------------------------------

def load(trace_dir) -> dict:
    """The ``repro.*`` host events (``program_marks``: name without the
    prefix, start, end in ns), each TPU program execution (``modules``:
    name without its ``(id)``, start, end, chip) and the benchmark's
    window span, from the newest profile under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not paths:
        return {"program_marks": [], "modules": [], "window": None}
    pd = ProfileData.from_file(str(paths[-1]))
    marks, modules, window = [], [], None
    for plane in pd.planes:
        m = trace.DEVICE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules.extend((e.name.split("(")[0], e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    int(m.group(1))) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("repro."):
                        marks.append((e.name[6:], e.start_ns,
                                      e.start_ns + e.duration_ns))
                    elif e.name == "bench.window":
                        window = (e.start_ns, e.start_ns + e.duration_ns)
    marks.sort(key=lambda x: (x[1], -x[2]))
    modules.sort(key=lambda x: x[1])
    return {"program_marks": marks, "modules": modules, "window": window}


def run_profile(ctx, bench_dir: Path):
    """``load`` of the profile this run wrote, found beside the
    checkout's benchmark files (``<checkout>/.bench_trace/``), or None
    when its window is not the one ``ctx["trace"]`` holds."""
    pf = load(Path(bench_dir).resolve().parent / ".bench_trace")
    w = trace.window(ctx["trace"])
    return pf if w is not None and pf["window"] == w else None


class Busy:
    """The union of device-busy intervals of a profile, for many
    queries: the busy seconds inside ``[a, b)``, averaged over chips as
    ``bench.trace.busy_ns`` reckons them."""

    def __init__(self, td: dict):
        self.n = max(td["devices"], 1)
        self.union = trace.union((a, b) for a, b, _, _ in td["ops"])
        per = {}
        for a, b, _, d in td["ops"]:
            per.setdefault(d, []).append((a, b))
        self.chips = [trace.union(v) for v in per.values()]
        self.starts = [[a for a, _ in u] for u in self.chips]

    def inside(self, a, b) -> float:
        total = 0
        for u, st in zip(self.chips, self.starts):
            i = max(bisect.bisect_right(st, a) - 1, 0)
            while i < len(u) and u[i][0] < b:
                total += max(0, min(u[i][1], b) - max(u[i][0], a))
                i += 1
        return total / self.n / 1e9

    def idle(self, lo, hi) -> list[tuple]:
        """The intervals of ``[lo, hi)`` in which no chip is busy."""
        edges = [lo] + [x for ab in trace.clipped(self.union, lo, hi)
                        for x in ab] + [hi]
        return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]


def innermost(marks, lo, hi) -> list[tuple]:
    """``[lo, hi)`` cut at every mark's edges, each piece named by the
    innermost mark that covers it (None outside every mark).  Marks nest,
    being the spans of one thread."""
    edges = sorted({lo, hi} | {x for _, a, b in marks for x in (a, b)
                               if lo < x < hi})
    starts = sorted(marks, key=lambda m: (m[1], -m[2]))
    out, stack, j = [], [], 0
    for x, y in zip(edges, edges[1:]):
        while j < len(starts) and starts[j][1] <= x:
            stack.append(starts[j])
            j += 1
        while stack and stack[-1][2] <= x:
            stack.pop()
        inner = next((m for m in reversed(stack) if m[2] > x), None)
        out.append((x, y, inner[0] if inner else None))
    return out


def idle_by_phase(td: dict, pf: dict) -> dict:
    """Device-idle seconds of the traced part, by the innermost program
    span the host was in (``outside`` for none: the harness's
    loop between steps)."""
    w = trace.window(td)
    if w is None or not td["ops"]:
        return {}
    idle = Busy(td).idle(*w)
    pieces = innermost(pf["program_marks"], *w)
    out: dict = {}
    i = 0
    for a, b in idle:
        while i < len(pieces) and pieces[i][1] <= a:
            i += 1
        k = i
        while k < len(pieces) and pieces[k][0] < b:
            x, y, name = pieces[k]
            s = (min(b, y) - max(a, x)) / 1e9
            key = name or "outside"
            out[key] = out.get(key, 0.0) + s
            k += 1
    return out


def idle_below_step_share(by_phase: dict) -> float:
    """The share of idle time that lies in a phase of a step, neither in
    the step's own self time nor outside every step."""
    total = sum(by_phase.values())
    below = sum(s for n, s in by_phase.items()
                if n not in ("engine.step", "outside"))
    return below / total if total else None


def _whole(pf: dict, td: dict, name: str) -> list[tuple]:
    w = trace.window(td)
    if w is None:
        return []
    return [m for m in pf["program_marks"]
            if m[0] == name and w[0] <= m[1] and m[2] <= w[1]]


def prefill_idle_ms_per_wave(td: dict, pf: dict):
    """Device-idle ms inside each ``engine.prefill`` lying wholly in the
    traced part, mean per wave; None when the part holds none."""
    waves = _whole(pf, td, "engine.prefill")
    if not waves or not td["ops"]:
        return None
    busy = Busy(td)
    return sum((b - a) / 1e9 - busy.inside(a, b)
               for _, a, b in waves) * 1e3 / len(waves)


def decode_idle_ms_per_step(td: dict, pf: dict):
    """Device-idle ms inside each ``engine.step`` of the traced part that
    holds a decode and neither a prefill nor a splice, per such step."""
    steps = _whole(pf, td, "engine.step")
    if not steps or not td["ops"]:
        return None
    marks = pf["program_marks"]
    starts = [m[1] for m in marks]
    busy = Busy(td)
    idle = []
    for _, a, b in steps:
        inside = {m[0] for m in marks[bisect.bisect_left(starts, a):
                                      bisect.bisect_left(starts, b)]
                  if m[2] <= b}
        if "engine.decode" in inside and not inside & {"engine.prefill",
                                                       "engine.splice"}:
            idle.append((b - a) / 1e9 - busy.inside(a, b))
    return sum(idle) * 1e3 / len(idle) if idle else None


def program_ms(td: dict, pf: dict, module: str = DECODE_PROGRAM):
    """Device time of each execution of the program ``module`` lying
    wholly in the traced part, found by its name, mean per call in ms."""
    w = trace.window(td)
    if w is None or not td["ops"]:
        return None
    busy = Busy(td)
    calls = [busy.inside(a, b) for n, a, b, _ in pf["modules"]
             if n == module and w[0] <= a and b <= w[1]]
    calls = [c for c in calls if c > 0]
    return sum(calls) * 1e3 / len(calls) if calls else None


# ---------------------------------------------------------------------------
# the log's records (host clock)
# ---------------------------------------------------------------------------

def _roots(records) -> list[int]:
    """The index of each record's outermost span."""
    out = []
    for i, (_, _, _, parent, _) in enumerate(records):
        out.append(i if parent is None else out[parent])
    return out


def window_steps(records, t0: float, t1: float) -> set:
    """Indices of the ``engine.step`` roots that start in ``[t0, t1)``."""
    return {i for i, (n, a, _, p, _) in enumerate(records)
            if n == "engine.step" and p is None and t0 <= a < t1}


def sched_ms_per_step(records, t0: float, t1: float):
    """Host ms in ``engine.schedule`` spans per window step."""
    steps = window_steps(records, t0, t1)
    if not steps:
        return None
    roots = _roots(records)
    s = sum(b - a for i, (n, a, b, _, _) in enumerate(records)
            if n == "engine.schedule" and roots[i] in steps)
    return s * 1e3 / len(steps)


def prefill_cache_loads_per_wave(records, t0: float, t1: float):
    """Compiles and persistent-cache loads counted inside the window's
    ``engine.prefill`` spans (and the spans within them), per wave."""
    steps = window_steps(records, t0, t1)
    roots = _roots(records)
    wave: list = []
    n = loads = 0
    for i, (name, _, _, parent, info) in enumerate(records):
        up = wave[parent] if parent is not None else None
        wave.append(i if name == "engine.prefill" else up)
        if roots[i] not in steps:
            continue
        n += name == "engine.prefill"
        if wave[i] is not None:
            loads += info.get("compiles", 0) + info.get("cache_loads", 0)
    return loads / n if n else None


def by_phase(records, t0: float, t1: float) -> dict:
    """Per span name, over the window's steps: self time (the span less
    its children) in ms per step, and the compiles and cache loads
    counted on it."""
    steps = window_steps(records, t0, t1)
    roots = _roots(records)
    self_s = [b - a for _, a, b, _, _ in records]
    for i, (_, a, b, parent, _) in enumerate(records):
        if parent is not None:
            self_s[parent] -= b - a
    out: dict = {}
    for i, (name, _, _, _, info) in enumerate(records):
        if roots[i] not in steps:
            continue
        o = out.setdefault(name, {"self_ms": 0.0, "compiles": 0,
                                  "cache_loads": 0})
        o["self_ms"] += self_s[i] * 1e3
        o["compiles"] += info.get("compiles", 0)
        o["cache_loads"] += info.get("cache_loads", 0)
    for o in out.values():
        o["self_ms"] /= max(len(steps), 1)
    return out


def slow_steps(records, t0: float, t1: float, over_s: float = SLOW_STEP_S
               ) -> list[dict]:
    """Every window step longer than ``over_s``, with its spans: name,
    start after the step's (ms), duration (ms) and ``info``."""
    steps = sorted(i for i in window_steps(records, t0, t1)
                   if records[i][2] - records[i][1] > over_s)
    roots = _roots(records)
    out = []
    for s in steps:
        a0 = records[s][1]
        out.append({"at_s": a0 - t0, "ms": (records[s][2] - a0) * 1e3,
                    "spans": [[n, (a - a0) * 1e3, (b - a) * 1e3, info]
                              for i, (n, a, b, _, info) in enumerate(records)
                              if roots[i] == s]})
    return out


def summary(records, t0: float, t1: float, td=None, pf=None) -> dict:
    """What one run's line prints: host readings over the window, and,
    given the profile, device readings over its traced part."""
    out = {"sched_ms_per_step": sched_ms_per_step(records, t0, t1),
           "prefill_cache_loads_per_wave":
               prefill_cache_loads_per_wave(records, t0, t1),
           "steps": len(window_steps(records, t0, t1)),
           "by_phase": by_phase(records, t0, t1),
           "slow_steps": slow_steps(records, t0, t1)}
    if td is not None and pf is not None:
        idle = idle_by_phase(td, pf)
        out.update({
            "prefill_idle_ms_per_wave": prefill_idle_ms_per_wave(td, pf),
            "decode_idle_ms_per_step": decode_idle_ms_per_step(td, pf),
            "decode_program_ms": program_ms(td, pf),
            "idle_s_by_phase": idle,
            "idle_below_step_share": idle_below_step_share(idle)})
    return out


# ---------------------------------------------------------------------------
# one run with the log attached
# ---------------------------------------------------------------------------

def run_attached(argv, *, root: Path = None, **kw) -> dict:
    """``bench/run.py``'s run with the program's span log attached after
    the warm-up; ``kw`` goes to ``harness.main`` (a test's CPU run).
    Returns its exit code, the log's records, the window on the host
    clock and, under ``--trace 1``, the profile's directory.

    This leans on where ``harness.main`` calls its ``fault`` hook: the
    window is taken as the hook's clock reading plus the ramp.  Once the
    harness attaches the log itself, this goes, and the window is the
    harness's own."""
    from bench import harness
    args = harness.parse(argv)
    layout = harness.Layout(root or harness.BENCH_DIR.parent)
    cell = harness.cell_of(layout.benchmark(), args.workload)
    ramp = float(layout.traffic(cell["traffic"]).get("ramp_s", 0.0))
    trace_dir = layout.root / ".bench_trace" / args.workload
    # cleared here, so that the window starts right after the hook
    shutil.rmtree(trace_dir, ignore_errors=True)
    got: dict = {}

    def attach(eng):
        from repro.core import trace as program_trace
        got["log"] = program_trace.SpanLog()
        program_trace.attach(got["log"])
        got["start"] = got["log"].clock()

    try:
        rc = harness.main(argv, root=root, fault=attach, **kw)
    finally:
        if "log" in got:
            from repro.core import trace as program_trace
            program_trace.detach()
    t0 = got["start"] + ramp
    return {"rc": rc, "records": got["log"].records, "t0": t0,
            "t1": t0 + args.seconds,
            "trace_dir": trace_dir if args.trace else None}


def main(argv=None, **kw) -> int:
    run = run_attached(argv, **kw)
    td = pf = None
    if run["trace_dir"] is not None:
        td, pf = trace.load(run["trace_dir"]), load(run["trace_dir"])
    print(json.dumps({"program_spans": summary(
        run["records"], run["t0"], run["t1"], td, pf)}), flush=True)
    return run["rc"]


if __name__ == "__main__":
    sys.exit(main())
