"""One run of one benchmark cell: set up, drive the window, check the
served tokens against the plain reference, print the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by its name (``Layout``): a cell names its
configuration and traffic in ``BENCHMARK.json``; the configuration file
names its reference and, where ``flops.py`` cannot count its blocks, its
count of operations and bytes (``counts/<count>.py``); the traffic file
names its generator; a per-layer metric ``a.b`` is read by
``metrics/a.py``.  So a later cell, mix or metric is added by adding
files and entries, not by editing these.

A traced run (``--trace 1``) attaches the program's span log
(``repro.core.trace.SpanLog``) from the end of the warm-up to the end of
the window, and hands its records to the readers; an untraced run
attaches nothing.

The window is open loop on the host clock: before each engine step every
request whose due time has passed is submitted, and after the step every
token that appeared is stamped.  A token is on the host when its step
returns (each step ends in a host read of the argmax), so the stamps are
what a client would see.  Each request is timed from its due time.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent


# ---------------------------------------------------------------------------
# finding files by name
# ---------------------------------------------------------------------------

class Layout:
    """Where a checkout keeps the benchmark's files."""

    def __init__(self, root: Path, bench: Path = None):
        self.root = Path(root)
        self.bench = Path(bench) if bench else self.root / "bench"

    def benchmark(self) -> dict:
        return json.loads((self.root / "BENCHMARK.json").read_text())

    def config(self, name: str) -> dict:
        return _json(self.bench / "configs" / f"{name}.json")

    def traffic(self, name: str) -> dict:
        return _json(self.bench / "traffic" / f"{name}.json")

    def generator(self, process: str):
        return _module(self.bench / "generators" / f"{process}.py")

    def reference(self, name: str):
        return _module(self.bench / "reference" / f"{name}.py")

    def count(self, name: str = None):
        """A configuration's count of operations and bytes: ``decode_flops``
        and ``decode_bytes`` as ``flops.py`` has them, from
        ``counts/<name>.py``, or ``flops.py`` itself for none (imported
        as ``bench.flops``, whose ``Uncounted`` the readers catch)."""
        if not name:
            from bench import flops
            return flops
        return _module(self.bench / "counts" / f"{name}.py")

    def metric(self, name: str):
        return _module(self.bench / "metrics" / f"{name.split('.')[0]}.py")

    def peaks(self) -> dict:
        return _json(self.bench / "peaks.json")


def _json(path: Path) -> dict:
    if not path.is_file():
        raise SystemExit(f"bench: no file {path}")
    return json.loads(path.read_text())


_MODULES: dict = {}


def _module(path: Path):
    """Import a file of the benchmark by its path (once per process)."""
    key = str(path.resolve())
    if key not in _MODULES:
        if not path.is_file():
            raise SystemExit(f"bench: no file {path}")
        name = "bench_" + "_".join(path.with_suffix("").parts[-2:])
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
        _MODULES[key] = mod
    return _MODULES[key]


def cell_of(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json")


def metrics_of(bench: dict, workload: str, kind: str) -> list[dict]:
    """The cell's end-to-end or per-layer metrics: those that list it,
    or that list no cells."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


# ---------------------------------------------------------------------------
# statistics
# ---------------------------------------------------------------------------

def percentile(xs, q: float):
    """Nearest rank: the smallest value with at least ``q`` percent of
    the sample at or below it (as the program's ``serving/workload.py``
    reckons it).  None for an empty sample."""
    if len(xs) == 0:
        return None
    s = sorted(xs)
    k = max(0, min(len(s) - 1, int(math.ceil(q / 100.0 * len(s))) - 1))
    return float(s[k])


# ---------------------------------------------------------------------------
# compile events (the listener of the program's chip_smoke.py)
# ---------------------------------------------------------------------------

class Compiles:
    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        self.hits = 0
        self.misses = 0

    def listen(self):
        from jax import monitoring

        def duration(event, secs, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs
                self.count += 1

        def count(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        monitoring.register_event_duration_secs_listener(duration)
        monitoring.register_event_listener(count)

    def compiled(self) -> int:
        """Programs XLA compiled: JAX reports a compile event for every
        program it needs, also those the persistent cache holds."""
        return self.count - self.hits

    def snapshot(self) -> dict:
        return {"compiles": self.count, "compile_s": self.seconds,
                "cache_hits": self.hits, "cache_misses": self.misses}


# ---------------------------------------------------------------------------
# spans from the benchmark's side of the calls into the program
# ---------------------------------------------------------------------------

class Spans:
    """Host spans on the host clock, each also a profiler annotation so
    that it shares the device trace's clock."""

    def __init__(self, clock):
        self.clock = clock
        self.items: list[tuple] = []     # (name, t0, t1, info)

    @contextlib.contextmanager
    def span(self, name: str, **info):
        import jax
        t0 = self.clock()
        with jax.profiler.TraceAnnotation("bench." + name):
            yield info
        self.items.append((name, t0, self.clock(), info))


class Proxy:
    """The backend, with a span around each call the engine makes."""

    def __init__(self, inner, spans: Spans, engine_ref: list):
        self._inner = inner
        self._spans = spans
        self._engine = engine_ref

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def prefill_wave(self, prompts):
        with self._spans.span("prefill_wave", n=len(prompts),
                              length=len(prompts[0])):
            return self._inner.prefill_wave(prompts)

    def decode(self, tokens, states):
        eng = self._engine[0]
        live = [s for s in range(len(tokens)) if eng.slot_req[s] is not None]
        lengths = getattr(states, "lengths", None)
        ctx = ([int(lengths[s]) for s in live] if lengths is not None
               else [0] * len(live))
        with self._spans.span("decode", rows=len(tokens), live=len(live),
                              ctx=ctx):
            return self._inner.decode(tokens, states)

    def splice(self, states, pairs):
        with self._spans.span("splice", n=len(pairs)):
            return self._inner.splice(states, pairs)

    def extract(self, states, slot):
        with self._spans.span("extract"):
            return self._inner.extract(states, slot)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def parse(argv):
    ap = argparse.ArgumentParser(
        prog="bench/run.py",
        description="Run one benchmark cell once and print its result "
                    "as the last line of standard output.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rate", type=float, default=None,
                    help="override the traffic file's mean arrival rate "
                         "(requests/s): for finding a cell's knee")
    ap.add_argument("--control", type=int, default=0, choices=(0, 1),
                    help="put the lower-precision control in the program's "
                         "place in the comparison (it must come out not "
                         "correct): for setting the limit, not for checks")
    ap.add_argument("--check", type=int, default=1, choices=(0, 1),
                    help="0 skips the comparison with the reference (the "
                         "run then reads not correct): for a knee sweep")
    ap.add_argument("--records", default=None,
                    help="write every window request's timings and every "
                         "step's duration, prefills and compiles to this "
                         "JSON file: for finding where a tail comes from")
    return ap.parse_args(argv)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def make_engine(cfg, conf: dict, traffic: dict, params, spans=None):
    """``ServingEngine`` over ``PagedJaxModelBackend``, with the program's
    defaults but what the configuration and the traffic fix."""
    from repro.serving import PagedJaxModelBackend, ServingEngine
    from repro.serving.workload import SLA_CLASSES
    srv = conf["serving"]
    backend = PagedJaxModelBackend(cfg, params, srv["cache_len"],
                                   page_size=srv["page_size"],
                                   slack_slots=srv["slack_slots"])
    ref: list = [None]
    if spans is not None:
        backend = Proxy(backend, spans, ref)
    opts = traffic.get("engine", {})
    sla = SLA_CLASSES if opts.get("sla_classes") else None
    eng = ServingEngine(cfg, params, n_slots=srv["n_slots"],
                        cache_len=srv["cache_len"], backend=backend,
                        sla_classes=sla,
                        preempt=bool(opts.get("preempt", False)))
    ref[0] = eng
    return eng


def warm(eng, traffic: dict, gen, vocab: int, n_slots: int, reqs,
         clock) -> dict:
    """Run every shape the window can form once, through the engine's own
    calls.  First each prompt length the mix allows, in admission waves
    of each size the traffic file lists (and of each gang size), which
    also warms the decode at the cell's slot count; then one more timed
    admission of each length.  Then each group of requests that one step
    may admit together in this seed's schedule (``admission_groups``):
    the program builds its KV page-in per group shape, so a group first
    met in the window would compile there."""
    shp = gen.shapes(traffic)
    waves = sorted(set(traffic.get("warm_waves", [1]))
                   | set(shp["gangs"]))
    rng = np.random.default_rng(0)
    steps, decode_s, admit_s = 0, [], {}

    def admit(lengths) -> float:
        """Submit ``lengths`` at once, serve them; the admitting step's
        seconds.  Three tokens each: the prefill's, one decoded in the
        admitting step, and one in a step that only decodes."""
        nonlocal steps
        for length in lengths:
            eng.submit(rng.integers(1, vocab, length), 3)
        took = []
        while not eng._drained():
            t = clock()
            eng.step()
            took.append(clock() - t)
        steps += len(took)
        decode_s.extend(took[1:])
        return took[0]

    for b in waves:
        b = min(b, n_slots)
        for length in shp["prompt_lengths"]:
            admit([length] * b)
    # timed once each length's programs are built: a first admission
    # compiles or loads them, and would make every step seem long
    for length in shp["prompt_lengths"]:
        admit_s[length] = admit([length])
    step_s = float(np.median(decode_s)) if decode_s else 0.0
    groups = admission_groups(reqs, admit_s, step_s, n_slots,
                              traffic.get("warm_scales", [1.0]),
                              float(traffic.get("warm_pairs_s", 0.0)))
    for g in groups:
        admit(list(g))
    return {"warm_steps": steps, "warm_groups": len(groups),
            "admit_s": admit_s, "step_s": step_s}


def admission_groups(reqs, admit_s: dict, step_s: float, n_slots: int,
                     scales, pairs_s: float = 0.0) -> list[tuple]:
    """The prompt lengths, in arrival order, of every group of two or
    more requests that one engine step may admit together.

    The driver submits what is due before each step, and a step admits
    everything waiting, so requests that fall due during one long step
    (one that prefills) are admitted together in the next.  This plays
    the schedule through a model of the engine: a step lasts ``step_s``,
    plus ``scale`` times each admitted length's measured extra; a slot
    holds its request for ``max_new`` steps.  It is played at each scale
    (the host's pace varies), and each group yields every run of two or
    more of its members in a row, since a group may form a step early or
    late.  A step can also last longer than any model of it (the host
    stands still now and then), so every two neighbours due within
    ``pairs_s`` seconds of each other count as a group too."""
    reqs = sorted(reqs, key=lambda r: r.due)
    out = {(len(a.prompt), len(b.prompt)) for a, b in zip(reqs, reqs[1:])
           if b.due - a.due <= pairs_s}
    for scale in scales:
        t, i, queue, live = 0.0, 0, [], []
        while i < len(reqs) or queue or live:
            while i < len(reqs) and reqs[i].due <= t:
                queue.append(reqs[i])
                i += 1
            if not queue and not live:
                t = reqs[i].due
                continue
            free = n_slots - len(live)
            taken, queue = queue[:free], queue[free:]
            g = tuple(len(r.prompt) for r in taken)
            out.update(g[a:b] for a in range(len(g))
                       for b in range(a + 2, len(g) + 1))
            t += step_s + scale * sum(admit_s[len(r.prompt)] - step_s
                                      for r in taken)
            live = [n - 1 for n in live] + [r.max_new - 2 for r in taken]
            live = [n for n in live if n > 0]
    return sorted(out)


class Driver:
    """Open loop from one thread: submit what is due, step, stamp."""

    def __init__(self, eng, reqs, t_start: float, clock, spans=None,
                 compiles=None):
        self.eng = eng
        self.clock = clock
        self.spans = spans
        self.compiles = compiles
        self.step_log: list = []     # (t0, t1, prefills, compiled, loads)
        self.recs = [{"due": r.due + t_start, "stamps": [], "rid": None,
                      "first_step": None, "req": r} for r in reqs]
        self.i = 0
        self.live: dict = {}                 # rid -> (record, Request)
        self.late: list = []                 # seconds each submit was late
        self.step_starts: list = []

    def run_until(self, t_end: float) -> None:
        eng, clock, recs = self.eng, self.clock, self.recs
        n = len(recs)
        while True:
            now = clock()
            if now >= t_end:
                return
            while self.i < n and recs[self.i]["due"] <= now:
                rec = recs[self.i]
                r = rec["req"]
                rec["rid"] = eng.submit(r.prompt, r.max_new, sla=r.sla,
                                        gang=r.gang)
                self.late.append(now - rec["due"])
                rec["late"] = now - rec["due"]
                self.live[rec["rid"]] = (rec, eng._reqs[rec["rid"]])
                self.i += 1
            if not self.live:
                # nothing to serve: wait for the next arrival
                nxt = recs[self.i]["due"] if self.i < n else t_end
                time.sleep(max(0.0, min(nxt, t_end) - clock()))
                continue
            cp = self.compiles
            before = (eng.stats.prefills, cp.compiled(), cp.hits) \
                if cp is not None else None
            t0 = clock()
            if self.spans is not None:
                with self.spans.span("step"):
                    eng.step()
            else:
                eng.step()
            t1 = clock()
            self.step_starts.append(t0)
            if cp is not None:
                self.step_log.append(
                    (t0, t1, eng.stats.prefills - before[0],
                     cp.compiled() - before[1], cp.hits - before[2]))
            for rid in list(self.live):
                rec, req = self.live[rid]
                got = len(req.out_tokens)
                have = len(rec["stamps"])
                if got > have:
                    if have == 0:
                        rec["first_step"] = t0
                    rec["stamps"].extend([t1] * (got - have))
                if req.done:
                    rec["out"] = np.asarray(req.out_tokens, np.int32)
                    del self.live[rid]


def end_to_end(recs, t0: float, t1: float) -> dict:
    """The window's numbers, over every request due in it."""
    due = [r for r in recs if t0 <= r["due"] < t1]
    ttft = [((r["stamps"][0] if r["stamps"] else t1) - r["due"]) * 1e3
            for r in due]
    gaps = []
    tokens = 0
    for r in recs:
        s = r["stamps"]
        tokens += sum(1 for x in s if t0 <= x < t1)
        gaps.extend((b - a) * 1e3 for a, b in zip(s, s[1:])
                    if t0 <= a and b < t1)
    mid = (t0 + t1) / 2
    halves = [[x for r, x in zip(due, ttft) if (r["due"] < mid) == h]
              for h in (True, False)]
    return {"attempted": len(due), "ttft_ms": ttft, "itl_ms": gaps,
            "ttft_p50_halves_ms": [percentile(h, 50) for h in halves],
            "unserved_at_end": sum(1 for r in due if not r["stamps"]),
            "tokens": tokens,
            "served_in_window": sum(1 for r in due if r["stamps"])}


def write_records(path, recs, step_log, pauses, t0: float,
                  t1: float) -> None:
    """Each window request's due time, lateness, sizes and first-token
    time, each step's duration, prefills, compiles and cache loads, and
    each garbage collection over a millisecond, in seconds from the
    window's start (durations in ms)."""
    reqs = [{"due": r["due"] - t0, "late": r.get("late"),
             "prompt": len(r["req"].prompt), "max_new": r["req"].max_new,
             "first_step": (r["first_step"] - t0
                            if r["first_step"] is not None else None),
             "ttft_ms": ((r["stamps"][0] if r["stamps"] else t1)
                         - r["due"]) * 1e3}
            for r in recs if t0 <= r["due"] < t1]
    steps = [[a - t0, (b - a) * 1e3, n, c, h]
             for a, b, n, c, h in step_log if t0 <= a < t1]
    gcs = [[a - t0, d * 1e3, g] for a, d, g in pauses
           if t0 <= a < t1 and d > 1e-3]
    Path(path).parent.mkdir(parents=True, exist_ok=True)
    Path(path).write_text(json.dumps({"requests": reqs, "steps": steps,
                                      "gc": gcs}))


def sample_finished(recs, seed: int, want_tokens: int, most: int):
    """Finished requests for the check, drawn from the seed: the longest
    one, then others until ``want_tokens`` served tokens."""
    done = [r for r in recs if "out" in r]
    if not done:
        return []
    rng = np.random.default_rng(seed ^ 0x5EED)
    longest = max(range(len(done)),
                  key=lambda j: len(done[j]["req"].prompt) + len(done[j]["out"]))
    order = [longest] + [j for j in rng.permutation(len(done))
                         if j != longest]
    pick, tok = [], 0
    for j in order:
        if len(pick) >= most or (tok >= want_tokens and pick):
            break
        pick.append(done[j])
        tok += len(done[j]["out"])
    return pick


def main(argv=None, *, root: Path = None, bench: Path = None,
         require_tpu: bool = True, cache: bool = True, fault=None,
         t_process: float = None, out: dict = None) -> int:
    """One run.  ``require_tpu=False`` and ``cache`` (a directory for
    the compile cache in place of the checkout's) let a test drive it on
    the CPU; ``fault(engine)`` breaks the served path underneath for a
    test of the check; ``out``, a dict, receives what the readers read
    (``ctx``), for a caller that reads more from the run."""
    t_process = time.perf_counter() if t_process is None else t_process
    clock = time.perf_counter
    args = parse(argv)
    layout = Layout(root or BENCH_DIR.parent, bench)
    spec = layout.benchmark()
    cell = cell_of(spec, args.workload)
    conf = layout.config(cell["config"])
    traffic = layout.traffic(cell["traffic"])
    if args.rate is not None:
        traffic["arrivals"]["rate"] = args.rate
    src = layout.root / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"bench: no program at {src / 'repro'}")
    sys.path.insert(0, str(src))

    import jax
    platform = jax.default_backend()
    if require_tpu and platform != "tpu":
        raise SystemExit(f"bench: needs a TPU; JAX's backend is {platform!r}")
    devices = jax.devices()
    if len(devices) < cell["chips"]:
        raise SystemExit(f"bench: cell needs {cell['chips']} chips; "
                         f"JAX sees {len(devices)}")
    dev = devices[0]
    peaks = layout.peaks()["devices"].get(dev.device_kind)
    if peaks is None and require_tpu:
        raise SystemExit(f"bench: no peaks for device {dev.device_kind!r} "
                         "in bench/peaks.json")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"]}

    cache_dir = None
    if cache:
        # the checkout's own cache, whatever the environment names, so
        # that two checkouts on one machine share nothing
        cache_dir = str(layout.root / ".jax_cache" if cache is True
                        else cache)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        # the page-in runs op by op: cache its small programs too
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        # no eviction: a cache that evicts scans every entry on each write
        # and may drop a program the next run needs
        jax.config.update("jax_compilation_cache_max_size", -1)
    compiles = Compiles()
    compiles.listen()

    from repro.models import api
    from repro.models.config import ModelConfig
    pconf = dict(conf["program"])
    pconf["block_pattern"] = tuple(pconf.get("block_pattern", ("attn",)))
    cfg = ModelConfig(**pconf)
    ref_mod = layout.reference(conf["reference"])
    count = layout.count(conf.get("count"))
    gen = layout.generator(traffic["process"])

    t = clock()
    from bench import weights
    params = weights.make_params(api.params_specs(cfg), args.seed,
                                 ref_mod.rule)
    jax.block_until_ready(params)
    t_weights = clock() - t
    spans = Spans(clock) if args.trace else None
    eng = make_engine(cfg, conf, traffic, params, spans)
    ramp = float(traffic.get("ramp_s", 0.0))
    reqs = gen.generate(traffic, args.seed, cfg.vocab, ramp + args.seconds)
    t = clock()
    warmed = warm(eng, traffic, gen, cfg.vocab, conf["serving"]["n_slots"],
                  reqs, clock)
    t_warm = clock() - t
    if spans is not None:
        spans.items.clear()
    log(json.dumps({"phase": "setup", "weights_s": t_weights,
                    "warm_s": t_warm, **warmed,
                    "compile_cache": cache_dir, **compiles.snapshot()}))

    if fault is not None:
        fault(eng)
    program_log = None
    if args.trace:
        from repro.core import trace as program_trace
        program_log = program_trace.SpanLog(clock)
        program_trace.attach(program_log)
    trace_dir = None
    trace_s = min(args.seconds, float(traffic.get("trace_s", args.seconds)))
    if args.trace:
        trace_dir = layout.root / ".bench_trace" / args.workload
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # the engine is Python: no per-call events
        opts.enable_hlo_proto = False
    t_start = clock()
    t0 = t_start + ramp
    t1 = t0 + args.seconds
    # the window is timed on the host clock; with --trace 1 its last
    # trace_s seconds also carry the profiler's window marker
    drv = Driver(eng, reqs, t_start, clock, spans,
                 compiles if args.records else None)
    pauses = []                            # (start, seconds, generation)
    if args.records:
        def gc_timer(phase, info, _t=[0.0]):
            if phase == "start":
                _t[0] = clock()
            else:
                pauses.append((_t[0], clock() - _t[0], info["generation"]))
        gc.callbacks.append(gc_timer)
    try:
        drv.run_until(t0)
        c0, h0 = compiles.compiled(), compiles.hits
        if args.trace:
            # the traced part is the window's last trace_s seconds:
            # stopping the profiler holds the host for seconds, which must
            # fall after the window; it starts a second early, so that its
            # own start stays outside the part it reads
            drv.run_until(t1 - trace_s - 1.0)
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            drv.run_until(t1 - trace_s)
            with jax.profiler.TraceAnnotation("bench.window"):
                drv.run_until(t1)
        drv.run_until(t1)
    finally:
        if program_log is not None:
            program_trace.detach()
    setup_s = t0 - t_process
    window_compiles = compiles.compiled() - c0
    window_loads = compiles.hits - h0
    if args.trace:
        jax.profiler.stop_trace()
    recs, late, step_starts = drv.recs, drv.late, drv.step_starts
    e2e = end_to_end(recs, t0, t1)
    mem = dev.memory_stats() or {}
    peak = mem.get("peak_bytes_in_use")
    counters = {"compiles_in_window": window_compiles,
                "cache_loads_in_window": window_loads,
                "late_p99_ms": percentile([x * 1e3 for x in late], 99),
                "steps": len([s for s in step_starts if t0 <= s < t1]),
                "engine": _engine_counters(eng)}
    log(json.dumps({"phase": "window", "attempted": e2e["attempted"],
                    "served": e2e["served_in_window"],
                    "tokens": e2e["tokens"],
                    "ttft_p50_ms": percentile(e2e["ttft_ms"], 50),
                    "ttft_p75_ms": percentile(e2e["ttft_ms"], 75),
                    "ttft_p90_ms": percentile(e2e["ttft_ms"], 90),
                    "ttft_p99_ms": percentile(e2e["ttft_ms"], 99),
                    "itl_p50_ms": percentile(e2e["itl_ms"], 50),
                    "itl_p99_ms": percentile(e2e["itl_ms"], 99),
                    "output_tok_s": e2e["tokens"] / args.seconds,
                    "gaps": len(e2e["itl_ms"]),
                    "ttft_p50_halves_ms": e2e["ttft_p50_halves_ms"],
                    "unserved_at_end": e2e["unserved_at_end"],
                    "rate": traffic["arrivals"]["rate"],
                    "memory_peak_bytes": peak, **counters}))

    if args.records:
        gc.callbacks.remove(gc_timer)
        write_records(args.records, recs, drv.step_log, pauses, t0, t1)

    # the program's state goes before the reference runs
    sample = sample_finished(recs, args.seed, int(conf["check"]["tokens"]),
                             int(conf["check"]["requests"]))
    samples = [(r["req"].prompt, r["out"]) for r in sample]
    short = sum(1 for r in recs if "out" in r
                and len(r["out"]) != r["req"].max_new)
    span_items = spans.items if spans is not None else []
    # readers of host spans and records see the whole window [t0, t1);
    # readers of the device trace see its traced part [trace_t0, t1)
    ctx = {"cfg": cfg, "conf": conf, "traffic": traffic, "peaks": peaks,
           "count": count, "recs": recs, "t0": t0, "t1": t1,
           "trace_t0": t1 - trace_s, "spans": span_items,
           "program_spans": (program_log.records if program_log is not None
                             else []),
           "counters": counters, "step_starts": step_starts,
           "trace_s": trace_s}
    del eng, drv, params, sample
    gc.collect()

    from bench.reference.common import served_gaps
    t = clock()
    got = served_gaps(ref_mod, conf["program"], args.seed, samples,
                      control=bool(args.control)
                      ) if samples and args.check else []
    ref_s = clock() - t
    gap = max((float(g.max()) for g, _ in got), default=None)
    extra = {"reference_s": ref_s, "sampled": len(samples),
             "program_gap": gap}
    if args.control:
        # the control stands in the program's place: its gap is compared
        gap = max((float(c.max()) for _, c in got), default=None)
        extra["control_gap"] = gap
    limit = float(conf["check"]["gap_limit"])
    checks = {
        "logit_gap": {"value": gap, "limit": limit},
        "served_tokens": {"value": int(sum(len(s) for _, s in samples)),
                          "limit": int(conf["check"]["min_tokens"])},
        "short_requests": {"value": short, "limit": 0},
    }
    correct = (gap is not None and gap <= limit
               and checks["served_tokens"]["value"]
               >= checks["served_tokens"]["limit"]
               and short == 0)
    log(json.dumps({"phase": "check", **extra}))

    result = {"correct": bool(correct), "attempted": e2e["attempted"],
              "failed": 0}
    if args.trace:
        from bench import trace as tr
        td = tr.load(trace_dir)
        ctx["trace"] = td
        metrics = {}
        for m in metrics_of(spec, args.workload, "per_layer"):
            v = layout.metric(m["name"]).read(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(tr.busy(td))
        result["breakdown"] = tr.breakdown(td)
    else:
        values = {"setup_s": setup_s,
                  "ttft_p50_ms": percentile(e2e["ttft_ms"], 50),
                  "ttft_p75_ms": percentile(e2e["ttft_ms"], 75),
                  "ttft_p90_ms": percentile(e2e["ttft_ms"], 90),
                  "itl_p99_ms": percentile(e2e["itl_ms"], 99),
                  "output_tok_s": e2e["tokens"] / args.seconds}
        metrics = {}
        for m in metrics_of(spec, args.workload, "end_to_end"):
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    device["memory_peak_bytes"] = peak
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    if out is not None:
        out.update(ctx)
    return 0


def _engine_counters(eng) -> dict:
    s = eng.stats
    return {"prefills": s.prefills, "prefill_waves": s.prefill_waves,
            "preemptions": s.preemptions, "kv_parks": s.kv_parks,
            "demotions": s.demotions, "completed": len(eng.completed)}
