"""Benchmark-regression gate: compare a BENCH json against the baseline.

Usage::

    python benchmarks/check_regression.py BASELINE.json CURRENT.json \
        [--tolerance 0.10] [--gain-tolerance 5.0] [--latency-tolerance 3.0] \
        [--throughput-tolerance 0.70] [--prefix table2/]

``--prefix`` restricts the gate to rows whose name starts with the given
prefix — for partial runs (e.g. ``serve_gangs.py --smoke`` writes only
``serve/`` rows; gating the full baseline against it would flag every
other row as missing).  A prefix that matches **zero** gated baseline rows
is a usage error (exit 2): a typo'd prefix must not silently gate nothing
and pass.

Four kinds of row are gated:

* ``kind == "speedup"`` (Table 2 + serving): the current speedup must be
  at least ``baseline * (1 - tolerance)`` — a *relative* band, because a
  15x conduction speedup and a 1.3x serving speedup tolerate
  proportionally similar jitter.
* ``kind == "gain_pct"`` (Fig 5): the current gain must be at least
  ``baseline - gain_tolerance`` — an *absolute* band in percentage
  points.  Gains are already ratios of two runtimes expressed in percent;
  a relative band would be meaninglessly tight near 0% and uselessly
  loose near 60%, so the band is points (default 5.0 — generous for a
  fully deterministic simulator, tight enough that a real placement
  regression, which historically costs 10+ points, still fails).
* ``kind == "latency"`` (the open-loop p99-TTFT rows): **lower is
  better** — the current value must be at most ``baseline +
  latency_tolerance``, an absolute band in the row's own units (engine
  steps; same spirit as the gain band: percentile latencies near zero
  would make any relative band meaningless).
* ``kind == "throughput"`` (the jax-serve tok/s rows): higher is better,
  relative floor ``baseline * (1 - throughput_tolerance)`` — but with a
  deliberately *wide* default band (0.70: the gate trips below 30% of
  baseline).  Unlike every other gated kind these rows are **wall-clock**
  measurements of real jitted model steps on shared CI runners, where
  2-3x machine-to-machine variance is normal and not a regression.  The
  failure mode worth gating is categorical collapse — a per-step
  recompile (stable jit signatures broken), a Python-loop fallback, an
  accidental O(n^2) splice — which costs 10x+, far outside any runner
  noise.  A tight band here would only train people to ignore the lane.

Wall-clock rows (``us_per_call``) are reported but not gated
— they are the only nondeterministic rows.  A gated baseline row that
disappears from the current run also fails (a silently dropped benchmark
is a regression in coverage).  New rows are allowed — commit a refreshed
baseline to start gating them.

Every gated row's report line carries its delta vs baseline (absolute and
percent), so the perf trajectory is readable straight from the CI job log
without diffing artifacts, and the same per-row deltas are written back
into the *current* ``BENCH_*.json`` under a top-level ``"deltas"`` key —
the artifact a CI run uploads then records not just what it measured but
how far it moved.  The write is best-effort: a read-only artifact degrades
to log-only, never to a gate failure.

Exit codes: 0 ok, 1 regression(s), 2 usage/IO error.  To refresh the
baseline after an intentional change::

    make bench-smoke && cp BENCH_smoke.json benchmarks/baseline_smoke.json
"""

from __future__ import annotations

import json
import sys

GATED_KINDS = ("speedup", "gain_pct", "latency", "throughput")


def load_rows(path: str) -> dict[str, dict]:
    with open(path) as f:
        doc = json.load(f)
    assert doc.get("schema") == 1, f"{path}: unknown schema {doc.get('schema')}"
    return {r["name"]: r for r in doc["rows"]}


def bound_for(row: dict, tolerance: float, gain_tolerance: float,
              latency_tolerance: float,
              throughput_tolerance: float) -> tuple[float, bool]:
    """The gate bound and its direction as ``(bound, lower_is_better)``:
    a relative floor for speedups, an absolute-points floor for gain
    percentages, an absolute-band *ceiling* for latency rows, and a wide
    relative floor for wall-clock throughput rows (see the module
    docstring for the rationale)."""
    if row.get("kind") == "latency":
        return row["value"] + latency_tolerance, True
    if row.get("kind") == "gain_pct":
        return row["value"] - gain_tolerance, False
    if row.get("kind") == "throughput":
        return row["value"] * (1.0 - throughput_tolerance), False
    return row["value"] * (1.0 - tolerance), False


def main(argv: list[str]) -> int:
    tolerance = 0.10
    gain_tolerance = 5.0
    latency_tolerance = 3.0
    throughput_tolerance = 0.70
    prefix = ""
    args = []
    i = 0
    while i < len(argv):
        if argv[i] in ("--tolerance", "--gain-tolerance",
                       "--latency-tolerance", "--throughput-tolerance"):
            flag = argv[i]
            if i + 1 >= len(argv):
                print(f"error: {flag} needs a value")
                return 2
            try:
                value = float(argv[i + 1])
            except ValueError:
                print(f"error: {flag} needs a number, got {argv[i + 1]!r}")
                return 2
            if flag == "--tolerance":
                tolerance = value
            elif flag == "--gain-tolerance":
                gain_tolerance = value
            elif flag == "--latency-tolerance":
                latency_tolerance = value
            else:
                throughput_tolerance = value
            i += 2
            continue
        if argv[i] == "--prefix":
            if i + 1 >= len(argv):
                print("error: --prefix needs a value")
                return 2
            prefix = argv[i + 1]
            i += 2
            continue
        if argv[i].startswith("--"):
            print(f"error: unknown flag {argv[i]}")
            return 2
        args.append(argv[i])
        i += 1
    if len(args) != 2:
        print(__doc__)
        return 2
    try:
        base = load_rows(args[0])
        cur = load_rows(args[1])
    except (OSError, json.JSONDecodeError, AssertionError) as e:
        print(f"error: {e}")
        return 2

    gated = sorted(name for name, row in base.items()
                   if row.get("kind") in GATED_KINDS
                   and name.startswith(prefix))
    if not gated:
        # a typo'd prefix would otherwise gate nothing and exit 0 — the
        # most dangerous way for a CI gate to "pass".  Distinguish the
        # no-prefix case so an operator is not sent hunting a flag they
        # never passed.
        if prefix:
            print(f"error: --prefix {prefix!r} matched no gated baseline "
                  f"rows in {args[0]} ({len(base)} rows total)")
        else:
            print(f"error: {args[0]} contains no gated rows "
                  f"(kinds {GATED_KINDS}; {len(base)} rows total)")
        return 2

    failures = []
    deltas = {}
    for name in gated:
        brow = base[name]
        crow = cur.get(name)
        if crow is None:
            failures.append(f"{name}: gated row missing from current run "
                            f"(baseline {brow['value']:.4f})")
            continue
        bound, lower_better = bound_for(brow, tolerance, gain_tolerance,
                                        latency_tolerance,
                                        throughput_tolerance)
        if lower_better:
            bad = crow["value"] > bound
            word, cmp = "ceil", ">"
        else:
            bad = crow["value"] < bound
            word, cmp = "floor", "<"
        status = "FAIL" if bad else "ok"
        delta = crow["value"] - brow["value"]
        pct = 100.0 * delta / brow["value"] if brow["value"] else 0.0
        deltas[name] = {"kind": brow.get("kind"), "base": brow["value"],
                        "cur": crow["value"], "delta": round(delta, 6),
                        "delta_pct": round(pct, 2), "status": status}
        print(f"{status:4s} {name:40s} base={brow['value']:8.4f} "
              f"cur={crow['value']:8.4f} d={delta:+8.4f} ({pct:+6.1f}%) "
              f"{word}={bound:8.4f}")
        if bad:
            band = "rel" if brow.get("kind") in ("speedup", "throughput") \
                else "abs"
            failures.append(
                f"{name}: {crow['value']:.4f} {cmp} {word} {bound:.4f} "
                f"(baseline {brow['value']:.4f}, {band} band)")
    for name in sorted(set(cur) - set(base)):
        if cur[name].get("kind") in GATED_KINDS and name.startswith(prefix):
            print(f"new  {name:40s} cur={cur[name]['value']:8.4f} "
                  "(ungated; refresh baseline to gate)")

    if deltas:
        # stamp the per-row deltas into the current artifact so a CI run's
        # uploaded BENCH_*.json records its movement vs baseline, not just
        # its raw values.  Best-effort: a read-only artifact is a logging
        # loss, not a gate failure.
        try:
            with open(args[1]) as f:
                doc = json.load(f)
            doc["deltas"] = {"baseline": args[0], "rows": deltas}
            with open(args[1], "w") as f:
                json.dump(doc, f, indent=1)
                f.write("\n")
        except OSError as e:
            print(f"note: could not write deltas into {args[1]}: {e}")

    print(f"\n{len(gated)} gated rows checked (speedup band {tolerance:.0%}, "
          f"gain band {gain_tolerance:g} points, "
          f"latency band {latency_tolerance:g} steps, "
          f"throughput band {throughput_tolerance:.0%}); "
          f"{len(failures)} regression(s)")
    for f in failures:
        print(f"REGRESSION: {f}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
