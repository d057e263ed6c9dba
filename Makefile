# Test tiers (see pytest.ini for the `slow` marker):
#   test-fast       — everything except the per-architecture smoke tests
#                     (~2-3 min; the CI push tier)
#   test-sharded    — the sharded-engine equivalence suite (including
#                     the wide-row cases) plus the wide-row suite on 8
#                     forced host devices (part of the CI push tier)
#   test-resilience — the fault-tolerance suite: crash-replay
#                     differential, degradation ladder, snapshot
#                     re-homing, on 8 forced host devices (CI sharded
#                     job)
#   test            — the full tier-1 command from ROADMAP.md (~4.5 min)
PYTEST = PYTHONPATH=src python -m pytest

.PHONY: test test-fast test-sharded test-resilience lint lint-ir \
	bench-backends bench-sharding bench-wide bench-arrange \
	bench-incremental bench-smoke trace-smoke

test:
	$(PYTEST) -x -q

# ruff lint (pyproject.toml [tool.ruff]); skipped with a notice when
# ruff is absent locally — CI installs it and fails properly
lint:
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check src benchmarks tests; \
	else \
		echo "ruff not installed; skipping lint (CI runs it)"; \
	fi

# static IR lint: compile the shared benchmark corpus, run the
# core.analysis verifier + worst-case bounds, exit nonzero on violations
lint-ir:
	PYTHONPATH=src python -m repro.analysis --corpus

test-fast:
	$(PYTEST) -x -q -m "not slow"

test-sharded:
	XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		$(PYTEST) -x -q tests/test_sharded.py tests/test_wide.py \
		tests/test_arrange.py tests/test_update_streams.py \
		tests/test_analysis.py

test-resilience:
	XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		$(PYTEST) -x -q tests/test_resilience.py

bench-backends:
	PYTHONPATH=src python -m benchmarks.run --only backends

bench-sharding:
	XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		PYTHONPATH=src python -m benchmarks.run --only sharding

bench-wide:
	PYTHONPATH=src python -m benchmarks.run --only wide

bench-arrange:
	PYTHONPATH=src python -m benchmarks.run --only arrange

# per-update maintenance latency vs batch recompute, single-device and
# 8-shard (forced host devices)
bench-incremental:
	XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		PYTHONPATH=src python -m benchmarks.run --only incremental

# CI push-tier bitrot guard: the bench harness end-to-end on tiny
# inputs, written to a scratch file so real results are not clobbered
bench-smoke:
	PYTHONPATH=src python -m benchmarks.run --smoke \
		--out results/bench-smoke.json

# observability smoke (CI bench-smoke job): run the 3-stratum demo
# fixpoint under jax.profiler and fail unless the profiler's record
# holds the engine's run, stratum and iteration spans — the profiler
# CLI and the spans cannot bitrot between perf PRs
trace-smoke:
	rm -rf results/trace-smoke
	PYTHONPATH=src python -m repro.observe --demo monitor --size 32 \
		--trace results/trace-smoke
