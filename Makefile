# Developer gates — counterpart of the reference's Makefile test target
# (foremast-barrelman/Makefile:5-8: generate/fmt/vet + go test ./...).
# CPU-pinned: every target here is a CPU recipe except `bench` and
# `chip-smoke`, which take whatever device JAX finds and hold it alone.

PY ?= python
CPU_ENV = env JAX_PLATFORMS=cpu

.PHONY: test test-fast lint native bench bench-smoke chip-smoke prewarm perf perf-smoke demo demo-hpa dryrun fuzz chaos soak soak-sharded soak-stream soak-restart soak-jobstore crashcheck clean

test: lint       ## full suite (CPU, 8 virtual devices via conftest), gated on lint
	$(PY) -m pytest tests/ -q

test-fast:       ## fail-fast variant for inner loops
	$(PY) -m pytest tests/ -x -q

lint:            ## invariant lint suite (devtools; docs/development.md) + ruff when installed
	$(PY) -m foremast_tpu.devtools
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check foremast_tpu tests; \
	else \
		echo "ruff not installed; skipped (pyproject [tool.ruff] is the config)"; \
	fi

native:          ## (re)build the C++ data-plane extension
	$(CPU_ENV) $(PY) -c "from foremast_tpu import native; assert native.available(), 'build failed'; print(native.lib_path())"

chip-smoke:      ## the main path end to end on the accelerator JAX finds (exits non-zero without one); run it through the chip tool
	$(PY) chip_smoke.py

bench:           ## the real benchmark (holds the accelerator; one JSON line; exits non-zero without a device number)
	$(PY) bench.py

bench-smoke:     ## bench plumbing check on CPU with tiny shapes
	$(CPU_ENV) BENCH_PAIRS_TOTAL=4000 BENCH_RUNS=20 BENCH_CYCLE_JOBS=500 $(PY) bench.py

prewarm:         ## compile the scoring-program grid into the persistent compile cache (JAX_COMPILATION_CACHE_DIR, else this checkout's .jax_cache/)
	$(CPU_ENV) $(PY) -m foremast_tpu prewarm

perf:            ## perf regression gates (zero steady-state recompiles, delta hit ratio >= 0.9, zero no-change launches, triage launch cut, streamed-ingest p99 <= 10s, mega-batch identity+win — all at byte-identical verdicts) + steady-state, streamed-ingest, cold-vs-warm-restart, mega-batch and fleet-simulator legs
	$(CPU_ENV) FOREMAST_PERF_STRICT=1 $(PY) -m pytest tests/ -m perf -q
	$(CPU_ENV) BENCH_CYCLE_STEADY=1 BENCH_CYCLE_JOBS=$${BENCH_CYCLE_JOBS:-500} BENCH_CYCLE_REPS=$${BENCH_CYCLE_REPS:-8} $(PY) -m foremast_tpu.bench_cycle
	$(CPU_ENV) BENCH_CYCLE_STREAM=1 BENCH_CYCLE_JOBS=$${BENCH_CYCLE_STREAM_JOBS:-200} $(PY) -m foremast_tpu.bench_cycle
	$(CPU_ENV) BENCH_CYCLE_RESTART=1 BENCH_CYCLE_JOBS=$${BENCH_CYCLE_RESTART_JOBS:-300} $(PY) -m foremast_tpu.bench_cycle
	$(CPU_ENV) BENCH_CYCLE_MEGABATCH=1 BENCH_CYCLE_JOBS=$${BENCH_CYCLE_MEGABATCH_JOBS:-5000} $(PY) -m foremast_tpu.bench_cycle
	$(CPU_ENV) BENCH_CYCLE_SIMFLEET=1 SIM_JOBS=$${SIM_JOBS:-5000} $(PY) -m foremast_tpu.bench_cycle

perf-smoke:      ## bounded per-PR mega-batch gate (CI): mini simfleet A/B identity + launch-count collapse on the launch-heavy shape (wall-clock win gated under FOREMAST_PERF_STRICT=1 in `make perf` — CI runners are too noisy for an 11% margin)
	$(CPU_ENV) $(PY) -m pytest tests/test_megabatch.py tests/test_simfleet.py -m perf -q

fuzz:            ## extended native-parser fuzz campaign (100k mutations)
	$(CPU_ENV) $(PY) tests/test_native_fuzz.py --child 100000

chaos:           ## seeded chaos soak: engine cycles under the fault plan
	$(CPU_ENV) $(PY) -m pytest tests/test_chaos_soak.py -m chaos -q

soak:            ## live-runtime chaos soak (<120s): spike+hang faults against a running process; health DEGRADED->OK end to end
	$(CPU_ENV) $(PY) -m pytest tests/test_soak_live.py -m chaos -q

soak-sharded:    ## multi-replica kill -9 chaos soak (<120s): 3 replicas over one archive, one hard-killed mid-cycle; zero lost / zero double-scored jobs, verdicts == single-replica baseline
	$(CPU_ENV) $(PY) -m pytest tests/test_shard_soak.py -q

soak-stream:     ## streaming-ingest soaks (<120s): push+poll under chaos latency and a store-shard brownout (stream-scoring through the blackout, DEGRADED->OK), plus the two-replica push-to-verdict trace soak (one trace across the ring forward, explain carries its trace_id)
	$(CPU_ENV) $(PY) -m pytest tests/test_stream_soak.py -q

soak-restart:    ## crash-durability soak (<60s): kill -9 a replica mid-push-stream, restart over the same WINDOW_STORE_DIR; WAL+segment replay, zero refetch storm, verdicts == never-restarted baseline (torn-WAL chaos leg included)
	$(CPU_ENV) $(PY) -m pytest tests/test_restart_soak.py -q

crashcheck:      ## exhaustive crash-point sweep (<60s): enumerate every durable-seam crossing in the winstore/jobstore/archive scenarios, SimulatedCrash at each one + every torn-tail byte cut, run the REAL recovery, assert record-or-effect, replay-twice == replay-once, and digest convergence; includes the seeded-bug selftest that must convict
	$(CPU_ENV) $(PY) -m foremast_tpu.devtools.crashcheck --scenario all

soak-jobstore:   ## job-store durability soak (<60s): kill -9 mid-transition with claimed leases over a JOB_STORE_DIR; WAL replay through the normal transition path, zero lost / zero double-scored jobs, provenance chains intact (disk-fault chaos leg + graceful-shutdown archive drain included)
	$(CPU_ENV) $(PY) -m pytest tests/test_jobstore_soak.py -q

demo:            ## hermetic rollback demo (no cluster)
	$(CPU_ENV) $(PY) -m foremast_tpu demo

demo-hpa:        ## hermetic autoscaling demo
	$(CPU_ENV) $(PY) -m foremast_tpu demo --hpa

dryrun:          ## multi-chip sharding dryrun on an 8-device virtual mesh
	$(CPU_ENV) $(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('dryrun ok')"

clean:
	rm -rf .pytest_cache build foremast_tpu.egg-info .jax_cache
	rm -f foremast_tpu/native/foremast_native-*.so
	find . -name __pycache__ -type d -exec rm -rf {} + 2>/dev/null || true
