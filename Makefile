# Convenience targets (CI runs scripts/tests.sh per matrix component)

.PHONY: test test-fast test-faults test-observability test-serve test-wire test-planner test-lifecycle test-lifecycle-faults test-analysis test-concurrency test-fleet-health test-slo test-precision test-chaos test-scale test-stream test-ingest test-perfmodel docs bench lint lint-gordo lockgraph-check image

test:
	python -m pytest tests/ -q

# The deterministic fault-injection robustness suite (crash+resume,
# bucket bisection, data-fetch retry) — CPU-only and not slow-marked,
# so the same tests also run inside the tier-1 `-m 'not slow'` budget.
test-faults:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m faults

# The build-telemetry suite: span recorder, live progress surface,
# compile/run attribution, Prometheus build metrics — CPU-only and not
# slow-marked, so the same tests also run inside the tier-1 budget.
test-observability:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m observability

# The micro-batching serving suite: flush policy, shape ladder, warmup,
# admission control, batched-vs-unbatched equivalence — CPU-only and not
# slow-marked, so the same tests also run inside the tier-1 budget.
test-serve:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m serve

# The columnar wire-format suite: content negotiation, JSON/Arrow
# codec parity (byte-identical JSON, numerically identical Arrow),
# malformed-body/406 contracts, mixed-format concurrency — CPU-only and
# not slow-marked, so the same tests also run inside the tier-1 budget.
test-wire:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m wire

# The build-planner suite: cost model + calibration, bucket packing,
# FleetPlan determinism/replay, plan-aware resume — CPU-only and not
# slow-marked, so the same tests also run inside the tier-1 budget.
test-planner:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m planner

# The self-healing lifecycle suite: drift statistics, canary
# publish/gates, promotion hot-swap, rollback + quarantine — CPU-only
# and not slow-marked, so the same tests also run inside tier-1.
test-lifecycle:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m lifecycle

# The deterministic lifecycle chaos drill: a crash injected at each
# lifecycle/serve fault site (drift_eval, canary_build, promote_swap,
# rollback) must leave serving on the last-good revision and the loop
# resumable.
test-lifecycle-faults:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m "lifecycle and faults"

# The fleet console suite: per-member health ledger, device-utilization
# telemetry, the joined fleet-status CLI/route surface — CPU-only and
# not slow-marked, so the same tests also run inside the tier-1 budget.
test-fleet-health:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m fleet_health

# The fleet SLO suite: cross-worker rollup reducer, burn-rate alert
# state machine, worker-sink merge, slo CLI/route/gauges — CPU-only and
# not slow-marked, so the same tests also run inside the tier-1 budget.
test-slo:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m slo

# The mixed-precision serving-ladder suite: precision vocabulary /
# casting / quantization units, engine e2e (f32 byte-parity, bf16
# verdict parity, degrade drill, mixed-precision hot swap), the
# precision-parity gate drills, and the cost-model precision features.
test-precision:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m precision

# The serving fault-containment suite: circuit-breaker state machine,
# batch bisection under injected device faults, NaN-poison detection,
# OOM rung demotion, the route-level chaos drills, and the
# breaker->lifecycle rebuild feed — CPU-only and not slow-marked, so
# the same tests also run inside the tier-1 budget.
test-chaos:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m chaos

# The streaming scoring-plane suite: row/event rings, SSE session
# replay + cursor resume, watermark scoring with breaker quarantine,
# backpressure shedding, hot-swap pinning, drain terminals, the three
# stream_* fault-site drills — CPU-only and not slow-marked, so the
# same tests also run inside the tier-1 budget.
test-stream:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m stream

# The device-resident ingest suite: compiled preprocessing plans,
# raw-column dlpack transfer with host fallback, compiled-vs-host
# parity across wire formats / batching modes / routes, ladder-snapped
# stream cuts — CPU-only and not slow-marked, so the same tests also
# run inside the tier-1 budget.
test-ingest:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m ingest

# The learned performance-model suite: trace harvesting, closed-form
# ridge fit + deterministic holdout, accuracy-gated promotion,
# cold-start/corrupt-table fallback, knob-off plan byte-parity, and the
# model-informed serving consumers — CPU-only and not slow-marked, so
# the same tests also run inside the tier-1 budget.
test-perfmodel:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m perfmodel

# The fleet-scale observability suite: sharded ledger layout/migration/
# dirty-flush contracts, rollup-manifest counting-open reads, bounded
# fleet-status selection/paging, the 5k-member breaker-summary guard —
# CPU-only and not slow-marked, so the same tests also run inside the
# tier-1 budget.
test-scale:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m scale

# The sub-5-minute tier: everything except the compile-heavy JAX suites
# (tests/parallel, tests/models) and slow-marked tests.
test-fast:
	bash scripts/tests.sh fast

image:
	docker build -t gordo-tpu-base:latest .

docs:
	python docs/generate_api.py docs/api
	python docs/generate_env_docs.py

# The invariant gate (gordo_tpu/analysis/): layering arrows, JAX
# hazards, env-knob registry, atomic writes, clock discipline,
# Prometheus cardinality, and the concurrency contracts (lock-guard
# inference, COW-publish discipline, fork-safety, thread lifecycle)
# over gordo_tpu/ itself — non-zero exit on any finding that is neither
# suppressed in-file nor justified in lint_baseline.json. CI's `lint`
# job runs exactly this (plus `--sarif` for the annotation artifact).
lint-gordo:
	python -m gordo_tpu lint

# The runtime half of the concurrency gate: run the threaded suites
# (serve, telemetry, lifecycle) with every lock instrumented
# (GORDO_TPU_LOCK_TRACE), then fail on any acquisition-ordering cycle —
# a cycle is two threads ordering the same locks differently, i.e. a
# deadlock waiting for the right interleaving. CI's `lint` job runs
# the same pair of steps.
lockgraph-check:
	rm -f lock_trace-*.jsonl
	JAX_PLATFORMS=cpu GORDO_TPU_LOCK_TRACE=lock_trace.jsonl \
		python -m pytest tests/serve tests/telemetry tests/lifecycle \
		-q -m 'not slow' -p no:cacheprovider
	python -m gordo_tpu lockgraph 'lock_trace-*.jsonl'

# The concurrency-contract suite: rule fixtures (lock-guard/COW/fork/
# thread-lifecycle), the lock-order harness unit tests, the COW
# hot-swap stress drill, the ledger/recorder fork drills, and the
# shutdown thread audit — CPU-only and not slow-marked, so the same
# tests also run inside the tier-1 budget.
test-concurrency:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m concurrency

# The static-analysis test suite: per-rule fixture trees, suppression/
# baseline semantics, and the tier-1 self-run asserting gordo_tpu/ is
# clean against the committed baseline.
test-analysis:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m analysis

bench:
	python bench.py

lint:
	python -m pytest tests/test_codestyle.py -q
