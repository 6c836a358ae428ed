# Tier-1 verification (mirrors .github/workflows/ci.yml)
PY ?= python

.PHONY: verify test bench bench-json resilience weak-scaling \
	check-pycache ci-local

verify: test bench

test:
	PYTHONPATH=src $(PY) -m pytest -x -q

bench:
	PYTHONPATH=src $(PY) -m benchmarks.run --fast

# full wall-clock benchmarks + BENCH_tick_loop.json (perf trajectory);
# --legacy-cpu pins the XLA CPU runtime the committed numbers use; the
# README bench table is regenerated from the fresh JSON (same bytes)
bench-json:
	PYTHONPATH=src $(PY) -m benchmarks.run --fast --json --legacy-cpu
	PYTHONPATH=src $(PY) -m benchmarks.render_bench_table

# fault-injection suite (incl. the multi-device elastic smoke — forced
# host-platform device count, subprocess-isolated) + resilience telemetry
# (BENCH_resilience.json: recall-vs-bit-flip-rate curves, rodent16
# drop-budget health report, device-loss recovery scenario) + the sanity
# gate on the fault-free recall path and the device-loss bitwise contract;
# mirrors the CI `resilience` job (see docs/RESILIENCE.md)
resilience:
	PYTHONPATH=src $(PY) -m pytest -x -q tests/test_resilience.py tests/test_checkpoint.py tests/test_elastic.py
	PYTHONPATH=src $(PY) -m benchmarks.resilience --legacy-cpu
	PYTHONPATH=src $(PY) -m benchmarks.check_resilience

# weak-scaling sweep of the sharded runtime (BENCH_weak_scaling.json):
# forced host-platform device counts 1/2/4 at fixed HCUs/device, sparse
# capacity-bounded spike exchange overlapped with the column phase, plus a
# mid-sweep elastic remesh leg; mirrors the CI `weak-scaling` job (see
# docs/BENCHMARKING.md for the JSON schema and the gated contract)
weak-scaling:
	PYTHONPATH=src $(PY) -m benchmarks.weak_scaling --legacy-cpu

# fail if bytecode artifacts ever get committed (nested __pycache__ dirs
# included); CI runs this in the `tests` job
check-pycache:
	@if git ls-files | grep -E '(^|/)__pycache__(/|$$)|\.py[co]$$'; then \
		echo "ERROR: tracked bytecode artifacts (see above)"; exit 1; \
	else echo "no tracked bytecode"; fi

# the exact CI sequence (tests + bench-gate + weak-scaling + resilience
# jobs), runnable locally so a gate failure can be reproduced without
# pushing: pycache guard -> README bench-table drift guard (BEFORE any
# bench regeneration — the table must match the COMMITTED JSON, and a
# fresh measurement would make it spuriously stale) -> tier-1 tests (incl.
# the flat-vs-blocked layout A/B fixture tests and the sparse-route
# capacity/drop tests) -> fast benchmarks -> tick-loop regression gate vs
# the COMMITTED JSON (taken from HEAD, not the working tree, so repeated
# runs cannot compound a slow drift past the gate; note the fresh
# measurement is left in BENCH_tick_loop.json afterwards, same as `make
# bench-json`) -> the Fig 10 layout benchmark (BENCH_layout.json: paper DRAM model + tile models +
# measured CPU flat/blocked A/B) + its layout-model gate -> the serving
# benchmark (BENCH_serving.json: continuous-batching recall QPS at
# rodent16) + its QPS-at-SLO gate -> the weak-scaling sweep
# (BENCH_weak_scaling.json) + its ratio/route-drop gate -> resilience
# telemetry + gate (the fault-injection tests already ran inside `test`)
ci-local: check-pycache
	PYTHONPATH=src $(PY) -m benchmarks.render_bench_table
	git diff --exit-code README.md
	$(MAKE) test bench
	git show HEAD:BENCH_tick_loop.json > /tmp/BENCH_committed.json
	git show HEAD:BENCH_serving.json > /tmp/BENCH_serving_committed.json
	git show HEAD:BENCH_layout.json > /tmp/BENCH_layout_committed.json
	git show HEAD:BENCH_weak_scaling.json > /tmp/BENCH_weak_committed.json
	PYTHONPATH=src $(PY) -m benchmarks.run --fast --json --legacy-cpu
	PYTHONPATH=src $(PY) -m benchmarks.check_regression \
		--committed /tmp/BENCH_committed.json
	PYTHONPATH=src $(PY) -m benchmarks.fig10_rowmerge --legacy-cpu
	PYTHONPATH=src $(PY) -m benchmarks.check_regression \
		--layout-committed /tmp/BENCH_layout_committed.json
	PYTHONPATH=src $(PY) -m benchmarks.serve_bcpnn --legacy-cpu
	PYTHONPATH=src $(PY) -m benchmarks.check_regression \
		--committed /tmp/BENCH_committed.json \
		--serving-committed /tmp/BENCH_serving_committed.json
	PYTHONPATH=src $(PY) -m benchmarks.weak_scaling --legacy-cpu
	PYTHONPATH=src $(PY) -m benchmarks.check_regression \
		--weak-scaling-committed /tmp/BENCH_weak_committed.json
	PYTHONPATH=src $(PY) -m benchmarks.resilience --legacy-cpu
	PYTHONPATH=src $(PY) -m benchmarks.check_resilience
