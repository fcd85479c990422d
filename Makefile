# Repro of "A Flexible Thread Scheduler for Hierarchical Multiprocessor
# Machines" — developer/CI entry points.
#
#   make test         tier-1 gate: the full pytest suite
#   make lint         fast syntax gate: byte-compile src/tests/benchmarks +
#                     docs-reference check (README/docs code pointers resolve)
#   make bench-smoke  seconds-scale benchmark sanity run (Table 2 conduction
#                     + imbalanced/thrash stealing rows + small Fig 5 sizes);
#                     writes machine-readable BENCH_smoke.json
#   make bench-gate   bench-smoke + regression check against the committed
#                     benchmarks/baseline_smoke.json (>10% speedup drop fails)
#   make serve-gate   stub-model serving benchmarks alone (gang + open-loop
#                     SLA + elastic + agentic rows; seconds, no jax) gated
#                     against the serve/ baseline rows
#   make jax-serve-gate  real-model serving lane: reduced zoo configs
#                     behind the dense AND paged jax backends (streams
#                     asserted identical, zero pool copies asserted);
#                     tok/s rows gated with the wide throughput band
#                     against benchmarks/baseline_jax.json
#   make golden-check regenerate the golden traces (simulator + serving
#                     engine) and fail on any drift
#   make bench        the full paper tables (slow: includes wall-clock
#                     Table 1)

PYTHON ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: test lint bench-smoke bench-gate serve-gate jax-serve-gate \
        golden-check bench

# tier-1 skips tests marked slow (the 7-minute ep_a2a compile test runs
# in its own non-required CI lane); override PYTEST_ARGS to change the cut
PYTEST_ARGS ?= -m "not slow"
test:
	$(PYTHON) -m pytest -x -q $(PYTEST_ARGS)

lint:
	$(PYTHON) -m compileall -q src tests benchmarks
	$(PYTHON) benchmarks/check_docs.py

bench-smoke:
	$(PYTHON) benchmarks/run.py --smoke --json BENCH_smoke.json

bench-gate: bench-smoke
	$(PYTHON) benchmarks/check_regression.py benchmarks/baseline_smoke.json BENCH_smoke.json

# order matters: serve_gangs' merge replaces every serve/ row, so the
# open-loop, elastic and agentic merges (which replace only their own
# rows) must run after it
serve-gate:
	$(PYTHON) benchmarks/serve_gangs.py --smoke --json BENCH_serve.json
	$(PYTHON) benchmarks/serve_open_loop.py --smoke --json BENCH_serve.json
	$(PYTHON) benchmarks/serve_elastic.py --smoke --json BENCH_serve.json
	$(PYTHON) benchmarks/serve_agentic.py --smoke --json BENCH_serve.json
	$(PYTHON) benchmarks/check_regression.py benchmarks/baseline_smoke.json BENCH_serve.json --prefix serve/

jax-serve-gate:
	$(PYTHON) benchmarks/serve_jax.py --smoke --json BENCH_jax.json
	$(PYTHON) benchmarks/check_regression.py benchmarks/baseline_jax.json BENCH_jax.json --prefix serve/jax_

# GOLDEN_OUT / SERVING_GOLDEN_OUT additionally write the regenerated
# dicts there (CI uploads them as the paste-ready artifacts on drift)
golden-check:
	$(PYTHON) tests/test_golden.py --check $(if $(GOLDEN_OUT),--out $(GOLDEN_OUT))
	$(PYTHON) tests/test_serving_golden.py --check $(if $(SERVING_GOLDEN_OUT),--out $(SERVING_GOLDEN_OUT))

bench:
	$(PYTHON) benchmarks/run.py
