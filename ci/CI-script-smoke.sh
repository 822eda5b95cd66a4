#!/usr/bin/env bash
# Fast gate: smoke tier minus the slow tail — tests measured >4s carry
# pytest.mark.slow and run only in the full tier. Measured (round 5,
# after re-tiering): 138 tests in ~82s cold on a 1-core worker (~30s of
# that is jax import + collection; under 60s on any multi-core box).
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
# audit_report.json: per-executable static FLOPs/bytes, the MFU
# roofline denominator for the BENCH captures.
JAX_PLATFORMS=cpu python -m fedml_tpu.cli audit --ci

python -m pytest tests/ -m "smoke and not slow" -q "$@"

# Round-pipeline smoke (K=2, 6 rounds, CPU): the async executor must run
# end-to-end through bench.py's pipeline phase child and emit the
# detail.pipeline contract keys. The contract lives in ONE place —
# tests/test_bench_contract.py — and is invoked here by node id (which
# runs it despite its slow marker, kept so the plain fast gate above
# doesn't pay the ~7s bench child twice).
python -m pytest \
  "tests/test_bench_contract.py::TestPhaseChild::test_pipeline_smoke_child_writes_valid_json" \
  -q -p no:cacheprovider

# Telemetry smoke (6 rounds, depth 4, flight recorder off vs on, CPU):
# the detail.telemetry contract keys must ship and host_syncs_per_round
# must be bit-identical with telemetry enabled — the "telemetry never
# adds a device fetch" guarantee, end-to-end through the bench child.
python -m pytest \
  "tests/test_bench_contract.py::TestPhaseChild::test_telemetry_smoke_child_writes_valid_json" \
  -q -p no:cacheprovider

# Serving smoke (two buckets, 2 hot-swaps, CPU, 8 virtual devices): the
# serving plane must run end-to-end through bench.py's serving phase
# child and emit the detail.serving contract keys — p50/p99 + req/s per
# bucket, exactly one jit trace per bucket across the swaps, a counted
# queue-full shed — PLUS the mesh/fleet gate: bitwise-identical
# responses across the (1,1) and (2,2) mesh shapes through 2 mid-run
# sharded hot swaps, and a 2-endpoint fleet routing within 2x load skew.
python -m pytest \
  "tests/test_bench_contract.py::TestPhaseChild::test_serving_smoke_child_writes_valid_json" \
  -q -p no:cacheprovider

# Chaos smoke (3 clients x 4 rounds, drop/dup/delay faults + one client
# kill + one server restart, CPU): the fault-tolerance layer must run
# end-to-end through bench.py's chaos phase child and emit the
# detail.chaos contract keys — run completes, every upload aggregated
# exactly once (telemetry counters), final params identical to a
# fault-free run of the same seed.
python -m pytest \
  "tests/test_bench_contract.py::TestPhaseChild::test_chaos_smoke_child_writes_valid_json" \
  -q -p no:cacheprovider

# Straggler smoke (4 clients x 3 rounds, CPU): the streaming
# aggregate-on-arrival tentpole must run end-to-end through bench.py's
# straggler phase child and emit the detail.straggler contract keys —
# sync-streaming final params bit-identical to the buffered baseline
# with server aggregation memory O(model), quorum rounds closing on
# quorum arrival past a 10x-delayed straggler and a killed client, and
# async mode folding every accepted update exactly once (WAL ledger ==
# telemetry counters) with oracle-matched staleness weights under
# drop/dup/delay faults and a server restart.
python -m pytest \
  "tests/test_bench_contract.py::TestPhaseChild::test_straggler_smoke_child_writes_valid_json" \
  -q -p no:cacheprovider

# Tracing smoke (3 clients x 6 rounds, ABBA off/on worlds, CPU): the
# distributed-tracing layer must run end-to-end through bench.py's
# tracing phase child and emit the detail.tracing contract keys —
# every comm send span flow-matched to its receive, per-round
# critical-path segments summing to round wall time, attributed
# tracing overhead within bound, aggregation bit-identical and
# host-syncs-per-round unchanged with tracing on.
python -m pytest \
  "tests/test_bench_contract.py::TestPhaseChild::test_tracing_smoke_child_writes_valid_json" \
  -q -p no:cacheprovider

# Defense smoke (6 clients x 6 rounds, poisoned worlds, CPU): Byzantine
# robustness on the streaming path must run end-to-end through
# bench.py's defense phase child and emit the detail.defense contract
# keys — norm-diff clipping bit-identical between stream and buffered
# with zero loud fallbacks, the undefended poisoned world diverging
# while the defended one (clipping + anomaly quarantine under drop/dup
# faults) recovers with the attacker ranks quarantined, async
# staleness-aware defenses reaching the fold target, and exactly-once
# fold accounting intact.
python -m pytest \
  "tests/test_bench_contract.py::TestPhaseChild::test_defense_smoke_child_writes_valid_json" \
  -q -p no:cacheprovider

# Chaos-plane smoke (determinism pair + exhaustive crash-point sweep +
# combined async/defense/registry world, CPU): the deterministic chaos
# plane must run end-to-end through bench.py's chaosplan phase child
# and emit the detail.chaosplan contract keys — an identical
# (ChaosSchedule, seed) pair reproducing the identical fault trace
# (telemetry counters + chaos.fault trace events), the server killed
# at EVERY enumerated WAL-append / checkpoint-publish write boundary
# with recovery and a clean InvariantChecker at each crash point, and
# the scripted-fault async world reaching its fold target with
# exactly-once folds proven from artifacts.
python -m pytest \
  "tests/test_bench_contract.py::TestPhaseChild::test_chaosplan_smoke_child_writes_valid_json" \
  -q -p no:cacheprovider

# Planet smoke (100k-client registry, 1k cohort x 3 rounds, CPU): the
# planet-scale population plane must run end-to-end through bench.py's
# planet phase child and emit the detail.planet contract keys —
# registry-backed rounds completing, warm-run peak-RSS delta flat in
# registry size (scales with the cohort), two-tier edge-tree
# aggregation bit-identical to the flat fold of the same terms, and
# the jit-trace census within the pow2 bucket budget.
python -m pytest \
  "tests/test_bench_contract.py::TestPhaseChild::test_planet_smoke_child_writes_valid_json" \
  -q -p no:cacheprovider

# Multichip smoke (8 forced host devices, cohort 16 x 3 rounds, CPU):
# the mesh-sharded federation must run end-to-end through bench.py's
# multichip phase child and emit the detail.multichip contract keys —
# rounds/s per (data, fsdp) mesh shape with EVERY sharded shape's
# final params bitwise identical to the single-chip vmap world
# (max_abs_diff == 0.0), one jit trace per shape, and the on-mesh
# streaming fold bitwise order-independent for raw and int8 uplinks.
# Host-transfer freedom of the mesh executables is the audit gate's
# half (fedml-tpu audit --ci above, simulation.round_fn_mesh).
python -m pytest \
  "tests/test_bench_contract.py::TestPhaseChild::test_multichip_smoke_child_writes_valid_json" \
  -q -p no:cacheprovider

# Hierarchical server plane smoke (3 clients/edge, edge_num 1/2/4,
# 3 rounds, CPU): edge aggregators as real ranks must run end-to-end
# through bench.py's hier phase child and emit the detail.hier
# contract keys — uploads/s scaling >= 2x from 1 to 4 edges under the
# deliberately slow root link (one scheduled delay per merged limb-set
# crossing the edge->root hop), tree-over-ranks final params
# bit-identical to the flat single-server world, and a mid-round edge
# kill/restart recovering bit-identically with the multi-tier
# InvariantChecker green on every world's artifacts.
python -m pytest \
  "tests/test_bench_contract.py::TestPhaseChild::test_hier_smoke_child_writes_valid_json" \
  -q -p no:cacheprovider

# Cross-device Beehive smoke (100k-device registry, cohort 64 x 3
# rounds, 30% scheduled mid-round vanish, CPU): the connectionless
# check-in plane must run end-to-end through bench.py's crossdevice
# phase child and emit the detail.crossdevice contract keys — every
# round closing on its fold target despite the churn, the
# pairwise-masked fold bitwise identical to the unmasked twin world
# (Shamir dropout recovery included), the WAL fold ledger matching the
# telemetry counters exactly, one jit trace per (speed tier, pow2
# bucket), and the InvariantChecker plus fedml-tpu check green on the
# run artifacts.
python -m pytest \
  "tests/test_bench_contract.py::TestPhaseChild::test_crossdevice_smoke_child_writes_valid_json" \
  -q -p no:cacheprovider

# Elastic-mesh preemption smoke (8 forced host devices, cohort 16 x 4
# rounds, CPU): the preemption-tolerance seam must run end-to-end
# through bench.py's elastic phase child and emit the detail.elastic
# contract keys — a scripted maintenance notice at round 1 draining
# the round, the WAL kind="preempt" record landing write-ahead of a
# forced checkpoint, the restart on 4 surviving devices restoring
# device-direct onto the reshaped mesh with the paired kind="resume"
# record, final params bitwise identical (max_abs_diff == 0.0) to the
# uninterrupted 8-device run, accumulator limbs traveling across the
# reshape identically for raw AND int8 uplinks, the InvariantChecker
# green on the preempt/resume ledger, and recovery_s in the headline.
python -m pytest \
  "tests/test_bench_contract.py::TestPhaseChild::test_elastic_smoke_child_writes_valid_json" \
  -q -p no:cacheprovider
