PYTHON ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: test test-slow test-all bench bench-full bench-kernels sweep \
	sweep-smoke trace bench-compare traffic

# Tier-1: fast suite (slow-marked full-size sims excluded via pyproject addopts)
test:
	$(PYTHON) -m pytest -x -q

# Only the slow full-size simulator tests
test-slow:
	$(PYTHON) -m pytest -q -m slow

# Everything
test-all:
	$(PYTHON) -m pytest -q -m ""

# Protocol-engine benchmark -> BENCH_protocol_engine.json
# (pagerank, srsp+rsp, n_wgs in {16,64,256}, serial vs batched engine)
bench:
	$(PYTHON) benchmarks/protocol_engine_bench.py --out BENCH_protocol_engine.json

# Full sweep incl. extra apps/scenarios; see --help for knobs
bench-full:
	$(PYTHON) benchmarks/protocol_engine_bench.py --apps pagerank sssp \
	  --scenarios baseline steal_only rsp srsp --out BENCH_protocol_engine.json

# Kernel micro-benchmarks (CSV to stdout): per-kernel jnp-reference wall
# times incl. the fused-turn trip-plan and plane-commit surfaces at
# n_wgs in {64,256,1024}, packed and boolean metadata layouts
bench-kernels:
	$(PYTHON) benchmarks/kernel_bench.py

# Workload-subsystem sweep: protocol x workload x n_agents grid plus the
# in-process engine A/Bs -> BENCH_workloads.json
# (schema: benchmarks/SCHEMA.md)
sweep:
	$(PYTHON) -m repro.workloads.sweep --out BENCH_workloads.json

# CI smoke: 1 replica, n_agents=16 grid, no serving cells — catches
# sweep-schema regressions in PR instead of at bench time.  Runs under
# REPRO_TRACE=1 so the schema-v6 latency columns and the Perfetto export
# are exercised too; benchmarks/check_smoke.py carries the structural
# assertions.  The committed BENCH_workloads.json comes from `make sweep`.
sweep-smoke:
	env REPRO_TRACE=1 $(PYTHON) -m repro.workloads.sweep --sizes 16 \
	  --seeds 1 --iters 1 --remote-batch-sizes 16 --no-fuse-ab --no-serving \
	  --out BENCH_workloads.smoke.json --trace-out TRACE_sweep.json
	$(PYTHON) benchmarks/check_smoke.py BENCH_workloads.smoke.json \
	  --expect-trace

# Trace-driven serving demo (DESIGN.md §13): generate + replay a
# Zipf-skewed bursty trace through kv_serving and print the request
# latency percentiles per scenario
traffic:
	$(PYTHON) examples/kv_serving_demo.py

# Trace the pinned crash-recovery demo cell and export Perfetto JSON
# (load TRACE_demo.json at https://ui.perfetto.dev); see README
# "Observability".
trace:
	$(PYTHON) -m repro.obs.report --demo --out TRACE_demo.json

# Bench regression gate: fresh smoke sweep vs the committed smoke
# baseline (BENCH_workloads.smoke.json).  Exits nonzero on regressed
# makespan / latency_p99 / srsp-vs-baseline ratios; CI runs the same
# diff with --advisory.
bench-compare:
	env REPRO_TRACE=1 $(PYTHON) -m repro.workloads.sweep --sizes 16 \
	  --seeds 1 --iters 1 --remote-batch-sizes 16 --no-fuse-ab --no-serving \
	  --out BENCH_workloads.smoke.new.json --trace-out TRACE_sweep.new.json
	$(PYTHON) benchmarks/compare.py BENCH_workloads.smoke.json \
	  BENCH_workloads.smoke.new.json
