# Tier-1 verification (ROADMAP.md).  -x fails fast; pytest exits non-zero
# on collection errors, so import-time breakage cannot hide behind a
# passing subset.  `make test` runs EVERYTHING and remains the union of
# what CI runs: ci.yml calls the lane targets below (test-lane-fast +
# test-kernels + test-mesh + test-audit), whose marker expressions all
# derive from the single KERNEL_MARKER/MESH_MARKER/AUDIT_MARKER variables
# — so the CI union stays provably equal to `make test` instead of
# drifting in two files.
#
# `make audit` runs the static hot-path auditor standalone (no pytest):
# compiles every serve-step cell (single-device + forced-8-device mesh),
# checks donation aliasing / pallas gather budget / dtype discipline /
# roofline conformance on the optimized HLO, and jaxlints src/repro.
# Exits non-zero on any unsuppressed finding.  The CI `audit` job runs
# the pytest lane (`make test-audit`), which drives the same matrix plus
# the injected-violation regression suite.
PY ?= python
# Every target here runs on the CPU, where the Pallas kernels must run in
# the interpreter (repro.kernels.interpret); on a TPU they compile.
export REPRO_PALLAS_INTERPRET ?= 1
# extra pytest flags (CI threads --junitxml=... through here)
PYTEST_FLAGS ?=

# ---- single source of truth for the test-lane markers -------------------
KERNEL_MARKER := kernel
MESH_MARKER := mesh
AUDIT_MARKER := audit
FAST_LANE_EXPR := not $(KERNEL_MARKER) and not $(MESH_MARKER) \
	and not $(AUDIT_MARKER)

.PHONY: test test-fast test-lane-fast test-kernels test-mesh test-audit \
	audit lint bench-serving bench-smoke bench-gate docs-check

test:
	PYTHONPATH=src $(PY) -m pytest -x -q $(PYTEST_FLAGS)

# CI lane 1: everything minus the kernel/mesh suites (their union with
# the two lanes below == `make test`).
test-lane-fast:
	PYTHONPATH=src $(PY) -m pytest -x -q -m "$(FAST_LANE_EXPR)" \
		$(PYTEST_FLAGS)

# CI lane 2: Pallas kernel oracle-parity suites alone
# (pl.pallas_call(interpret=True) on CPU — they EXECUTE, not skip).
test-kernels:
	PYTHONPATH=src $(PY) -m pytest -q -m "$(KERNEL_MARKER)" $(PYTEST_FLAGS)

# CI lane 3: multi-device sharded-serving parity suites.  The forced
# host-platform device count makes the sharded paths EXECUTE on a
# CPU-only box; the suites' subprocess drivers also force it themselves,
# so they pass under plain `make test` too.
test-mesh:
	XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		PYTHONPATH=src $(PY) -m pytest -q -m "$(MESH_MARKER)" \
		$(PYTEST_FLAGS)

# CI lane 4: the static hot-path auditor suite (compile-only conformance
# checks + injected-violation regressions; the mesh cells run through a
# subprocess that forces 8 host devices itself).
test-audit:
	PYTHONPATH=src $(PY) -m pytest -q -m "$(AUDIT_MARKER)" $(PYTEST_FLAGS)

# Standalone auditor run (same checks, direct CLI output, no pytest).
audit:
	$(PY) scripts/audit_steps.py --matrix all

# Inner-loop development: the fast lane minus the slow dry-run compile
# cells on top.
test-fast:
	PYTHONPATH=src $(PY) -m pytest -x -q -m "$(FAST_LANE_EXPR)" \
		--ignore=tests/test_dryrun_small.py $(PYTEST_FLAGS)

# Lint gate (CI `lint` job; ruff ships via requirements-dev.txt).
# `ruff check` runs the error-class rules everywhere; `ruff format
# --check` is a RATCHET — FORMAT_PATHS lists the files already
# formatted, grow it file by file as they are cleaned up.
# Remaining outside the ratchet: tests/ and src/repro/ outside
# analysis/.
FORMAT_PATHS := \
	benchmarks/bench_fig2_ordering.py \
	benchmarks/bench_fig3_ops_mem.py \
	benchmarks/bench_fig4_oi.py \
	benchmarks/bench_fig5_throughput.py \
	benchmarks/bench_fig6_energy.py \
	benchmarks/bench_kernels.py \
	benchmarks/bench_serving.py \
	benchmarks/bench_table1_params.py \
	benchmarks/check_regression.py \
	benchmarks/common.py \
	benchmarks/roofline_report.py \
	benchmarks/run.py \
	scripts/audit_steps.py \
	scripts/check_docs.py \
	scripts/junit_summary.py \
	src/repro/analysis/__init__.py \
	src/repro/analysis/audit.py \
	src/repro/analysis/audit_allowlist.py \
	src/repro/analysis/hlo.py \
	src/repro/analysis/jaxlint.py
lint:
	ruff check .
	ruff format --check $(FORMAT_PATHS)

bench-serving:
	PYTHONPATH=src $(PY) benchmarks/bench_serving.py --requests 12 --steps 200

# Tiny CPU config wired into CI (exits non-zero if any serving check
# regresses: prefix hit rate, prefill-token/block savings, bounded
# prefill compiles, utilization vs the contiguous baseline, sharded-row
# token parity + per-device paged-byte scaling, spec-decode parity +
# acceptance + modeled amortization, telemetry parity + trace validity +
# disabled-mode overhead).  Artifacts include
# trace_serving.json / metrics_serving.json / bench_drift.json.
bench-smoke:
	PYTHONPATH=src $(PY) benchmarks/bench_serving.py --requests 6 \
		--max-batch 2 --block-size 8 --prefill-chunk 8 \
		--shared-prefix-len 16 --steps 300

# CI `bench-gate` job: run the smoke bench, then diff its JSON artifacts
# against the committed baselines (benchmarks/baselines/) with
# per-metric tolerances.  Refresh after an intentional perf change with
# `python benchmarks/check_regression.py --update`.
bench-gate: bench-smoke
	$(PY) benchmarks/check_regression.py

# CI `docs` job: intra-repo markdown links resolve, the README flag
# table covers every launch/serve.py flag, and the serving CLIs'
# module docstrings document their own argparse (static — no jax).
docs-check:
	$(PY) scripts/check_docs.py
