#!/usr/bin/env bash
#
# Per-layer test runner — the CI matrix entry point (reference analog:
# /root/reference/scripts/tests.sh, which splits the suite into per-layer
# jobs precisely so no single job pays the whole suite's wall time).
#
#   scripts/tests.sh <component>
#
# Components mirror the package layers, plus:
#   fast     — the sub-5-minute tier: every layer EXCEPT the
#              compile-heavy JAX suites (tests/parallel, tests/models,
#              tests/server — the serving suites pay LSTM fleet-compile
#              fixtures) and everything marked slow. Tiering is by
#              path, like the reference's, because compile cost tracks
#              the directory; each excluded directory has its own
#              matrix job. Measured 2026-07-30: ~4 min on a 1-core
#              host.
#   parallel — the compile-heavy fleet/mesh/distributed suite in its own
#              job (~7 min single-core).
#   models   — estimator/training/anomaly suites (JAX compiles, TF
#              parity tests auto-skip without tensorflow).
#   allelse  — anything not covered by a named component, so a new test
#              directory can never silently fall out of CI.
#   all      — the whole non-slow suite (what `make test` runs).

set -euo pipefail
cd "$(dirname "$0")/.."

# Tests force the CPU backend themselves (tests/conftest.py); the env
# vars here only make that explicit for CI logs and virtualize an
# 8-device mesh for the sharding suites.
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
export XLA_FLAGS="${XLA_FLAGS:---xla_force_host_platform_device_count=8}"

run() { python -m pytest -q "$@"; }

component="${1:-all}"
case "$component" in
    all)      run -m "not slow" tests/ ;;
    fast)     run -m "not slow" tests/ --ignore=tests/parallel --ignore=tests/models --ignore=tests/server --ignore=tests/serve --ignore=tests/lifecycle ;;
    # The parallel job runs its compile-heavy suites INCLUDING the
    # slow-marked sequence fleet module — that is exactly
    # why it has its own matrix job; only the multi-process distributed
    # tests (their own `slow` cost class, run by the `slow` component)
    # are excluded here.
    parallel) run tests/parallel --ignore=tests/parallel/test_distributed.py ;;
    models)   run -m "not slow" tests/models ;;
    builder)  run -m "not slow" tests/builder ;;
    cli)      run -m "not slow" tests/cli ;;
    client)   run -m "not slow" tests/client ;;
    dataset)  run -m "not slow" tests/dataset ;;
    machine)  run -m "not slow" tests/machine ;;
    ops)      run -m "not slow" tests/ops ;;
    reporters) run -m "not slow" tests/reporters ;;
    serializer) run -m "not slow" tests/serializer ;;
    server)   run -m "not slow" tests/server ;;
    serve)    run -m "not slow" tests/serve ;;
    planner)  run -m "not slow" tests/planner ;;
    lifecycle) run -m "not slow" tests/lifecycle ;;
    analysis) run -m "not slow" tests/analysis ;;
    # The fleet-console suite cuts across tests/telemetry, tests/server
    # and tests/lifecycle — marker-selected so its own matrix job stays
    # meaningful while the per-directory jobs still run every test.
    fleet_health) run -m "fleet_health and not slow" tests/ ;;
    # The SLO suite cuts across tests/telemetry, tests/server and
    # tests/lifecycle the same way — marker-selected.
    slo)      run -m "slo and not slow" tests/ ;;
    # The columnar wire suite cuts across tests/server and
    # tests/telemetry — marker-selected like fleet_health/slo.
    wire)     run -m "wire and not slow" tests/ ;;
    # The concurrency-contract suite cuts across tests/analysis,
    # tests/server and tests/serve — marker-selected the same way.
    concurrency) run -m "concurrency and not slow" tests/ ;;
    # The mixed-precision suite cuts across tests/serve, tests/models,
    # tests/lifecycle, tests/planner and tests/telemetry —
    # marker-selected like fleet_health/slo/wire/concurrency.
    precision) run -m "precision and not slow" tests/ ;;
    # The serving fault-containment suite cuts across tests/serve,
    # tests/server, tests/telemetry and tests/lifecycle —
    # marker-selected the same way.
    chaos)    run -m "chaos and not slow" tests/ ;;
    # The streaming scoring-plane suite cuts across tests/stream,
    # tests/server and tests/telemetry (the PR 18 observability layer:
    # stream spans in rollups, freshness/integrity SLOs, the bounded
    # scrape collector) — marker-selected the same way.
    stream)   run -m "stream and not slow" tests/ ;;
    # The fleet-scale observability suite (sharded ledger, rollup
    # manifest, bounded fleet-status, breaker summaries) lives in
    # tests/telemetry + tests/server — marker-selected the same way.
    scale)    run -m "scale and not slow" tests/ ;;
    # The learned performance-model suite cuts across tests/perfmodel,
    # tests/ingest (ladder-snapped stream cuts) and the planner/serve
    # consumer contracts — marker-selected the same way.
    perfmodel) run -m "perfmodel and not slow" tests/ ;;
    # The device-resident ingest suite cuts across tests/ingest,
    # tests/server and tests/serve (compiled plans, raw-column
    # transfer, parity, stream snap) — marker-selected the same way.
    ingest)   run -m "ingest and not slow" tests/ ;;
    utils)    run -m "not slow" tests/utils ;;
    workflow) run -m "not slow" tests/workflow ;;
    formatting) run tests/test_codestyle.py ;;
    docs)     run tests/test_docs.py ;;
    slow)     run -m "slow" tests/ ;;
    allelse)
        run -m "not slow" tests/ \
            --ignore=tests/analysis \
            --ignore=tests/builder --ignore=tests/cli --ignore=tests/client \
            --ignore=tests/dataset --ignore=tests/lifecycle \
            --ignore=tests/machine --ignore=tests/models \
            --ignore=tests/ops --ignore=tests/parallel --ignore=tests/planner \
            --ignore=tests/reporters --ignore=tests/serializer \
            --ignore=tests/serve --ignore=tests/server \
            --ignore=tests/utils --ignore=tests/workflow
        ;;
    *)
        echo "unknown component: $component" >&2
        exit 2
        ;;
esac
