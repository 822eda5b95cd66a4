#!/usr/bin/env bash
# Fast gate: smoke tier minus the slow tail — tests measured >4s carry
# pytest.mark.slow and run only in the full tier, except the scenario
# worlds that hold a plane's end-to-end gate (exactly-once ledgers,
# bitwise identities, crash recovery), which stay in the fast tier.
# Re-measure with --durations=40 and re-tier when the gate drifts.
set -e
cd "$(dirname "$0")/.."

# Static-analysis gate (fedml_tpu/analysis — docs/static_analysis.md):
# pure-AST, no JAX import, runs in seconds. Ratcheted against the
# checked-in lint_baseline.json: any NEW finding (hidden host sync /
# retrace hazard / missed donation / unseeded randomness / swallowed
# exception / unlocked cross-thread state / registry drift) fails, and
# so does a STALE baseline entry — fixing a finding must shrink the
# baseline in the same change.
python -m fedml_tpu.cli lint --ci

# Compiled-artifact audit gate (fedml_tpu/analysis/compiled.py +
# audit.py — docs/static_analysis.md): AOT-lowers every registered
# hot-path executable (round fn, aggregation term/fold jits, planet
# group jit, serving forward) across the pow2 shape census — NOTHING
# executes, no data exists — and verifies donation aliasing,
# host-transfer freedom, census size and baked-constant budgets
# against the checked-in audit_baseline.json (new findings AND stale
# entries both fail; --update-baseline is rejected here). Also emits
# audit_report.json: per-executable static FLOPs/bytes.
JAX_PLATFORMS=cpu python -m fedml_tpu.cli audit --ci

# Every plane's gates (one trace per bucket, host syncs identical with
# telemetry on, stream == buffered, exactly-once under faults, server
# and edge restart, every crash point, tree == flat, every mesh shape
# == single chip, preempt -> resume on half the devices, masked ==
# unmasked) are tests of the plane's own file and carry the smoke mark.
python -m pytest tests/ -m "smoke and not slow" -q "$@"
