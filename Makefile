# Tier-1 verification (ROADMAP.md): CPU-only, wall-clock bounded so the
# eager-loop regression class (host-synced peel rounds) is caught
# mechanically — a hung or quadratically-slow suite fails, not stalls.
# Budgets re-baselined for PR 3: the facade parity matrix adds ~2 engine
# compiles per fixture cell to the full suite (fast lane carries only the
# (2,3) column) plus the sharded-combo matrix in the slow lane.
VERIFY_BUDGET ?= 3300
FAST_BUDGET ?= 2100

.PHONY: verify verify-core verify-facade verify-fast bench quick-bench \
	regen-golden smoke bench-build calibrate kernel-tests lint-nucleus

verify:
	JAX_PLATFORMS=cpu PYTHONPATH=src timeout $(VERIFY_BUDGET) \
		python -m pytest -x -q

# the full suite split in two so the facade/golden chunk's long-standing
# interpreter-teardown segfault (exit 139 AFTER all tests pass — a CPython
# finalization flake, not a test failure) cannot mask the rest of tier-1:
# verify-core is everything else and must be green; verify-facade is just
# the two facade-parity files, isolated so a rerun/triage targets them.
verify-core:
	JAX_PLATFORMS=cpu PYTHONPATH=src timeout $(VERIFY_BUDGET) \
		python -m pytest -x -q \
		--ignore=tests/test_facade.py --ignore=tests/test_golden.py

verify-facade:
	JAX_PLATFORMS=cpu PYTHONPATH=src timeout $(VERIFY_BUDGET) \
		python -m pytest -x -q tests/test_facade.py tests/test_golden.py

# the push lane: everything not marked slow (no subprocess meshes, no
# hypothesis fuzzing) — CI runs this on every push, the full suite in a
# second job
verify-fast:
	JAX_PLATFORMS=cpu PYTHONPATH=src timeout $(FAST_BUDGET) \
		python -m pytest -x -q -m "not slow"

# nucleuslint (DESIGN.md §12): the jit/trace/concurrency static-analysis
# gate — fails on any finding not in the committed baseline.  Pure stdlib
# (no jax import), so it needs no accelerator deps and runs in seconds.
# LINT_FLAGS="--json findings.json --dead --dead-json dead.json" in CI.
LINT_FLAGS ?=
lint-nucleus:
	PYTHONPATH=src timeout 300 python -m repro.analysis src/repro \
		$(LINT_FLAGS)

bench:
	JAX_PLATFORMS=cpu PYTHONPATH=src python -m benchmarks.run

quick-bench:
	JAX_PLATFORMS=cpu PYTHONPATH=src python -m benchmarks.run --quick

# the Pallas kernel suite alone (interpret mode on CPU): the CI fast lane
# runs this as an explicit first step so a kernel-vs-oracle divergence is
# named in the job log before the full matrix runs
kernel-tests:
	JAX_PLATFORMS=cpu PYTHONPATH=src timeout 900 \
		python -m pytest -x -q -m "not slow" \
		tests/test_kernels.py tests/test_peel_round.py

# measure this device's planner crossovers (tiny_nr, the use_pallas=None
# verdict) and write the profile resolve_plan loads; the committed
# src/repro/core/planner_profile.json is this target's output on the
# reference CPU container.  CALIBRATE_FLAGS="--quick" for the CI smoke.
CALIBRATE_OUT ?= src/repro/core/planner_profile.json
CALIBRATE_FLAGS ?=
calibrate:
	JAX_PLATFORMS=cpu PYTHONPATH=src timeout 1800 \
		python tools/calibrate_planner.py --out $(CALIBRATE_OUT) \
		$(CALIBRATE_FLAGS)

# rewrite tests/golden/*.json from the oracle-pinned gather+replay path;
# the JSON diff is the review artifact for any intentional semantic change
regen-golden:
	JAX_PLATFORMS=cpu PYTHONPATH=src python tools/regen_golden.py

# chunked-incidence-builder CI gate: ba4k with a deliberately tiny memory
# budget — fails on any deviation from the golden build fingerprint or a
# >20% budget overshoot (tools/check_build_budget.py; DESIGN.md §7)
bench-build:
	JAX_PLATFORMS=cpu PYTHONPATH=src timeout 900 \
		python tools/check_build_budget.py

# examples + nucleus-serving smoke: drives the decompose() facade end-to-end
# with the repo's legacy-surface DeprecationWarnings escalated to errors, so
# any in-repo code that regresses onto the deprecated per-function surface
# fails here (DESIGN.md §6).  The filter is message-scoped to the wrappers'
# "repro.core.<name> is deprecated" prefix — dependency churn emitting its
# own DeprecationWarnings must not redden this lane.
SMOKE_W = PYTHONWARNINGS="error:repro.core:DeprecationWarning"
smoke:
	JAX_PLATFORMS=cpu PYTHONPATH=src $(SMOKE_W) timeout 600 \
		python examples/quickstart.py --n 200
	JAX_PLATFORMS=cpu PYTHONPATH=src $(SMOKE_W) timeout 900 \
		python examples/graph_pipeline.py
	JAX_PLATFORMS=cpu PYTHONPATH=src $(SMOKE_W) timeout 300 \
		python -m repro.launch.serve --arch nucleus --queries 64
	JAX_PLATFORMS=cpu PYTHONPATH=src $(SMOKE_W) timeout 600 \
		python -m repro.launch.serve --arch nucleus --warm-pool \
		--pool-graphs 4 --queries 32 --r 2,2 --s 3,4
	JAX_PLATFORMS=cpu PYTHONPATH=src $(SMOKE_W) timeout 900 \
		python -m repro.launch.serve --arch nucleus --server --selftest \
		--cache-dir .jax_cache/server-manifest
