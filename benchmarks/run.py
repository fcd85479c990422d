"""Benchmark aggregator: one module per paper table/figure.

Prints ``name,value,derived`` CSV rows (value unit depends on the bench:
us/call for Table 1, speedup for Table 2, gain-% for Fig 5).

``--smoke`` runs a seconds-scale subset (conduction-only Table 2 with the
imbalanced + thrash stealing sections, small Fig 5 sizes, the stub-model
serving-gang rows, no wall-clock Table 1) — the CI sanity
target — and writes a machine-readable ``BENCH_smoke.json`` (override the
path with ``--json PATH``; pass ``--json`` in non-smoke mode to capture
the full run).  Schema::

    {"schema": 1, "suite": "smoke"|"full",
     "rows": [{"name": "table2/thrash_adaptive", "value": 10.26,
               "kind": "speedup"|"gain_pct"|"latency"|"throughput"
                       |"us_per_call",
               "derived": "...",
               "counters": {"steals": ..., "steals_by_level": {...},
                            "rebalances": ..., "steal_cost": ...}}]}

``counters`` is present on Table 2 rows only.  The ``bench-gate`` CI job
feeds this file to ``benchmarks/check_regression.py`` against the committed
``benchmarks/baseline_smoke.json`` — speedup rows regressing more than the
tolerance band fail the build.

The real-model serving lane (``serve_jax.py``, kind ``throughput``) is
deliberately NOT in this aggregator: it jits actual model steps, so it
lives in its own CI job (``jax-serve-gate``) with its own baseline
(``baseline_jax.json``) and a much wider band — see that module.
"""

from __future__ import annotations

import json
import os
import sys
import traceback

# make `benchmarks` and `repro` importable when invoked directly as
# `python benchmarks/run.py`, with or without PYTHONPATH=src
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "src"))

# value unit per benchmark module (JSON row "kind")
_KINDS = {"table1": "us_per_call", "table2": "speedup", "fig5": "gain_pct",
          "serve": "speedup"}


def _json_path(argv: list[str], smoke: bool):
    if "--json" in argv:
        i = argv.index("--json")
        if i + 1 < len(argv) and not argv[i + 1].startswith("-"):
            return argv[i + 1]
        return "BENCH_smoke.json"
    return "BENCH_smoke.json" if smoke else None


def main() -> None:
    argv = sys.argv[1:]
    smoke = "--smoke" in argv
    json_path = _json_path(argv, smoke)
    from benchmarks import (fig5_fibonacci, serve_agentic, serve_elastic,
                            serve_gangs, serve_open_loop, table2_conduction)

    if smoke:
        mods = [table2_conduction, fig5_fibonacci, serve_gangs,
                serve_open_loop, serve_elastic, serve_agentic]
    else:
        from benchmarks import table1_cost
        mods = [table1_cost, table2_conduction, fig5_fibonacci,
                serve_gangs, serve_open_loop, serve_elastic, serve_agentic]

    failed = 0
    out_rows = []
    for mod in mods:
        try:
            rows = mod.run(smoke=True) if smoke else mod.run()
            for row in rows:
                name, v, d = row[:3]
                counters = row[3] if len(row) > 3 else None
                # optional per-row kind override (5th element) — the
                # open-loop bench mixes lower-is-better "latency" rows
                # into a prefix whose default kind is "speedup"
                kind = row[4] if len(row) > 4 else \
                    _KINDS.get(name.split("/")[0], "value")
                print(f"{name},{v:.4f},{d}")
                entry = {"name": name, "value": round(v, 6),
                         "kind": kind, "derived": d}
                if counters:
                    entry["counters"] = counters
                out_rows.append(entry)
        except Exception:
            traceback.print_exc()
            failed += 1
    if json_path and out_rows:
        with open(json_path, "w") as f:
            json.dump({"schema": 1, "suite": "smoke" if smoke else "full",
                       "rows": out_rows}, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"# wrote {json_path} ({len(out_rows)} rows)", file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
