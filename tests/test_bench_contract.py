"""bench.py contract: ONE JSON line with the required schema, a parent
that runs every phase and fails when a child fails, and working
``--cpu`` phase children (the CI smoke shapes).
"""

import json
import os
import subprocess
import sys
import tempfile

import pytest

pytestmark = pytest.mark.smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")

sys.path.insert(0, REPO)
import bench  # noqa: E402


class TestSchema:
    def test_headline_cohorts_match_for_bf16_comparability(self):
        # run_bf16's speedup_vs_f32 is only meaningful if both phases
        # time the SAME cohort
        assert bench._headline_cohort(True) == bench._headline_cohort(True)
        assert bench._headline_cohort(False) == bench._headline_cohort(False)

    def test_mfu_detail_known_and_unknown_kind(self):
        out = bench._mfu_detail.__doc__
        assert "static estimate" in out  # honesty marker stays

    def test_sweep_cohorts_sorted_smallest_first(self):
        # retention base = smallest cohort
        assert bench._SWEEP_COHORTS == sorted(bench._SWEEP_COHORTS)

    def test_pipeline_depths_pinned(self):
        assert bench._PIPELINE_KS == (1, 2, 4)

    def test_parent_runs_every_phase(self):
        """Every phase of the child vocabulary is run by the parent:
        headline first, the detail.<phase> records in order, then the
        sweep and the three phases the parent stitches against the
        headline (bf16 speedup, longctx, mesh vs vmap engine)."""
        import inspect

        stitched = {"headline", "sweep", "bf16", "longctx", "mesh"}
        assert set(bench._DETAIL_PHASES) | stitched == set(bench.PHASE_CHOICES)
        assert not set(bench._DETAIL_PHASES) & stitched
        parent = inspect.getsource(bench.main)
        for phase in stitched:
            assert f'"{phase}"' in parent, phase

    def test_cpu_children_force_the_host_devices_they_need(self):
        """serving needs 8 forced host devices for its (1,1)-vs-(2,2)
        submeshes, multichip the full 8-device (data, fsdp) world,
        elastic 8 so the scripted loss is a real 8 -> 4 reshape, mesh 2;
        everything else runs on 1."""
        assert bench._CPU_DEVICES == {
            "mesh": 2, "multichip": 8, "serving": 8, "elastic": 8,
        }
        assert set(bench._CPU_DEVICES) <= set(bench.PHASE_CHOICES)


class TestPhaseChild:
    def _run_child(self, phase: str, timeout: int, smoke: bool = False) -> dict:
        """Invoke one --cpu phase child exactly as the CI smoke script
        does and return its JSON — ONE copy of the invocation contract,
        so a changed flag or env requirement breaks every phase test."""
        with tempfile.NamedTemporaryFile("r", suffix=".json", delete=False) as f:
            out = f.name
        cmd = [sys.executable, BENCH, "--phase", phase, "--cpu"]
        if smoke:
            cmd.append("--smoke")
        try:
            r = subprocess.run(
                cmd + ["--out", out],
                capture_output=True, text=True, timeout=timeout, cwd=REPO,
            )
            assert r.returncode == 0, r.stderr[-800:]
            with open(out) as fh:
                return json.load(fh)
        finally:
            os.unlink(out)

    @pytest.mark.slow  # subprocess + jax import + tiny interpret run
    def test_longctx_cpu_child_writes_valid_json(self):
        d = self._run_child("longctx", 240)
        for k in ("flash_ms", "naive_ms", "flash_speedup_vs_naive",
                  "score_matrix_mb_avoided"):
            assert k in d

    @pytest.mark.slow  # ~6.5s bench child; the fast gate runs the same
    # invocation once via ci/CI-script-smoke.sh's dedicated smoke block
    def test_pipeline_smoke_child_writes_valid_json(self):
        """The CI smoke invocation (K=2, 6 rounds, CPU): the executor
        runs end-to-end and emits the detail.pipeline contract keys."""
        d = self._run_child("pipeline", 420, smoke=True)
        assert d["k2"]["rounds_per_sec"] > 0
        assert d["k2"]["host_syncs_per_round"] is not None
        assert d["rounds_timed"] == 6

    @pytest.mark.slow  # subprocess + three full K-depth runs
    def test_pipeline_cpu_child_reports_all_depths(self):
        d = self._run_child("pipeline", 420)
        for k in ("k1", "k2", "k4"):
            assert d[k]["rounds_per_sec"] > 0, d
        assert "speedup_k4_vs_k1" in d

    @pytest.mark.slow  # ~10s bench child; the fast gate runs the same
    # invocation once via ci/CI-script-smoke.sh's telemetry smoke block
    def test_telemetry_smoke_child_writes_valid_json(self):
        """The CI telemetry smoke invocation (6 rounds, depth 4, CPU):
        the flight recorder runs end-to-end through bench.py's
        telemetry phase child and emits the detail.telemetry contract
        keys — both timings, the overhead figure, the host-sync
        bit-identity flag, and a non-empty exported trace."""
        d = self._run_child("telemetry", 420, smoke=True)
        assert d["rounds_timed"] == 6 and d["pipeline_depth"] == 4
        for mode in ("off", "on"):
            assert d[mode]["rounds_per_sec"] > 0
            assert d[mode]["host_syncs_per_round"] is not None
        assert "overhead_pct" in d
        assert d["host_syncs_match"] is True
        assert d["trace_events"] > 0

    @pytest.mark.slow  # ~8s bench child; the fast gate runs the same
    # invocation once via ci/CI-script-smoke.sh's serving smoke block
    def test_serving_smoke_child_writes_valid_json(self):
        """The CI serving smoke invocation (two buckets, 2 hot-swaps,
        CPU): the serving plane runs end-to-end through bench.py's
        serving phase child and emits the detail.serving contract keys
        — p50/p99 latency and req/s for at least two batch buckets,
        exactly one jit trace per bucket across the whole run including
        the hot swaps, and a counted queue-full shed."""
        d = self._run_child("serving", 420, smoke=True)
        assert len(d["buckets"]) >= 2, d
        for b, stats in d["buckets"].items():
            assert stats["p50_ms"] > 0 and stats["p99_ms"] >= stats["p50_ms"]
            assert stats["req_per_sec"] > 0
            assert stats["jit_traces"] == 1, (b, stats)
        assert d["swaps"] >= 2
        assert d["one_trace_per_bucket"] is True
        assert d["shed_queue_full"] > 0
        # mesh variant: the SAME requests at two mesh shapes, bitwise-
        # identical responses across 2 mid-run hot swaps, one trace
        # per serve bucket per shape
        mesh = d["mesh"]
        assert len(mesh["shapes"]) >= 2, mesh
        for key, s in mesh["shapes"].items():
            assert s["swaps"] == 2, (key, s)
            assert s["one_trace_per_bucket"] is True, (key, s)
            assert s["p99_ms"] > 0 and s["req_per_sec"] > 0
        assert mesh["max_abs_diff_across_shapes"] == 0.0
        assert mesh["bitwise_identical_across_shapes"] is True
        # fleet variant: two endpoints behind one door, load-aware
        # routing within the 2x skew gate, a mid-run fleet-wide swap
        fleet = d["fleet"]
        assert fleet["endpoints"] == 2
        assert sum(fleet["routed"]) > 0
        assert fleet["load_skew"] <= 2.0
        assert fleet["depth_max"] >= 1
        assert fleet["occupancy_frac"] is None or fleet["occupancy_frac"] > 0
        assert fleet["swaps"] >= 1
        assert fleet["p99_ms"] > 0 and fleet["req_per_sec"] > 0

    @pytest.mark.slow  # ~15s bench child; the fast gate runs the same
    # invocation once via ci/CI-script-smoke.sh's chaos smoke block
    def test_chaos_smoke_child_writes_valid_json(self):
        """The CI chaos smoke invocation (3 clients x 4 rounds, CPU):
        the fault-tolerance layer runs end-to-end through bench.py's
        chaos phase child — drop/dup/delay faults, one client kill
        (replacement RESYNCed into the pending round), one server
        crash + checkpoint/WAL restart — and emits the detail.chaos
        contract keys with the exactly-once and params-identity
        acceptance evidence."""
        d = self._run_child("chaos", 420, smoke=True)
        assert d["rounds_completed"] == d["rounds"]
        assert d["client_killed"] is True
        assert d["server_restarted"] is True
        assert d["server_resumed_at_round"] == d["rounds"] - 1
        assert d["wal_records"] == d["rounds"]
        # the acceptance criteria as numbers: retransmits + dedups
        # actually happened, every upload aggregated exactly once, and
        # the final params are bit-identical to the fault-free run
        assert d["retries_total"] > 0
        assert d["dup_dropped_total"] > 0
        assert d["resyncs_total"] >= 1
        assert d["uploads_aggregated"] == d["expected_uploads"]
        assert d["exactly_once"] is True
        assert d["max_abs_diff_vs_clean"] == 0.0
        assert d["params_match_clean"] is True
        # the post-hoc InvariantChecker replays the world's artifacts
        assert d["invariants_ok"] is True, d["invariants_violations"]
        assert "cohort_accounting" in d["invariants_checked"]

    @pytest.mark.slow  # ~2min bench child; the fast gate runs the same
    # invocation once via ci/CI-script-smoke.sh's straggler smoke block
    def test_straggler_smoke_child_writes_valid_json(self):
        """The CI straggler smoke invocation (4 clients x 3 rounds,
        CPU): the streaming-aggregation tentpole runs end-to-end
        through bench.py's straggler phase child — buffered baseline,
        bit-identical sync streaming, quorum close past a delayed + a
        killed client, async exactly-once under faults + restart —
        and emits the detail.straggler contract keys."""
        d = self._run_child("straggler", 500, smoke=True)
        # sync streaming: bit-identity at O(model) memory
        assert d["stream_identical_to_buffered"] is True
        assert d["max_abs_diff_stream_vs_buffered"] == 0.0
        assert d["stream_peak_buffered"] == 0
        assert d["buffered_peak_buffered"] == d["clients"]
        # quorum: rounds complete on quorum arrival, not the straggler
        q = d["quorum"]
        assert q["rounds_completed"] == d["rounds"]
        assert q["quorum_closes"] >= 1
        assert q["deaths"] == 1  # the kill -9'd client was declared
        assert q["stragglers_dropped"] >= 1
        assert q["tracks_quorum_not_straggler"] is True
        assert q["wall_s"] < q["blocked_wall_bound_s"]
        assert q["peak_buffered"] == 0
        assert q["invariants_ok"] is True, q["invariants_violations"]
        # async: exactly-once folds + staleness oracle across a restart
        a = d["async"]
        assert a["server_restarted"] is True
        assert a["client_killed"] is True
        assert a["folds_total"] >= a["target_folds"]
        assert a["publishes"] >= 2
        assert a["double_folds"] == 0
        assert a["refolded_across_restart"] == 0
        assert a["folds_counter_total"] == a["wal_folded_pairs"]
        assert a["exactly_once"] is True
        assert a["stale_folds"] >= 1
        assert a["staleness_weights_match_oracle"] is True
        assert a["invariants_ok"] is True, a["invariants_violations"]
        assert "exactly_once_folds" in a["invariants_checked"]

    @pytest.mark.slow  # ~60s bench child; the fast gate runs the same
    # invocation once via ci/CI-script-smoke.sh's defense smoke block
    def test_defense_smoke_child_writes_valid_json(self):
        """The CI defense smoke invocation (6 clients x 6 rounds,
        poisoned worlds, CPU): Byzantine robustness runs end-to-end
        through bench.py's defense phase child — clip bit-identity,
        undefended divergence, defended recovery with quarantine under
        drop/dup faults, async staleness-aware defenses — and emits the
        detail.defense contract keys."""
        d = self._run_child("defense", 500, smoke=True)
        # streamable clipping: bit-identity at O(model) memory, no
        # loud buffered fallback for a clipping config
        assert d["clip_stream_identical_to_buffered"] is True
        assert d["max_abs_diff_clip_stream_vs_buffered"] == 0.0
        assert d["clip_stream_fallbacks"] == 0
        assert d["clip_stream_peak_buffered"] == 0
        assert d["clip_buffered_peak_buffered"] == d["clients"]
        assert d["clipped_uploads"] > 0
        # the poisoned world hurts without a defense...
        assert d["undefended_diverges"] is True
        assert d["undefended_loss"] > 3.0 * d["clean_loss"]
        # ...and the defended world recovers: attacker ranks
        # quarantined, rounds keep completing through the
        # drop-expected path, model back within bound of clean
        assert d["attackers_quarantined"] is True
        assert set(d["attacker_ranks"]) <= set(d["quarantined_ranks"])
        assert d["rounds_completed"] == d["rounds"]
        assert d["defended_within_bound"] is True
        assert d["defended_loss"] < 0.5 * d["undefended_loss"]
        assert d["defense_clipped_total"] > 0
        assert d["quarantine_rejected_uploads"] >= 1
        # exactly-once accounting survives dup faults + quarantine
        assert d["exactly_once"] is True
        assert d["folds_total"] == d["uploads_aggregated"]
        assert d["invariants_ok"] is True, d["invariants_violations"]
        # async: the construction-time rejection is gone — defenses
        # run per fold, the attacker is quarantined, folds hit target
        a = d["async"]
        assert a["attacker_quarantined"] is True
        assert a["folds_total"] >= a["target_folds"]
        assert a["clipped_uploads"] > 0
        assert a["quarantine_rejected_uploads"] >= 1
        assert a["defended_within_bound"] is True
        assert a["invariants_ok"] is True, a["invariants_violations"]

    @pytest.mark.slow  # ~60s bench child; the fast gate runs the same
    # invocation once via ci/CI-script-smoke.sh's chaosplan smoke block
    def test_chaosplan_smoke_child_writes_valid_json(self):
        """The CI chaosplan smoke invocation (CPU): the deterministic
        chaos plane runs end-to-end through bench.py's chaosplan phase
        child and emits the detail.chaosplan contract keys — the
        determinism pair reproducing an identical fault trace from the
        same (schedule, seed), the crash-point sweep killing the server
        at EVERY enumerated WAL-append / checkpoint-publish boundary
        with recovery and clean invariants at each, and the combined
        async+defense+registry world reaching its fold target under
        scripted multi-layer faults with the InvariantChecker clean."""
        d = self._run_child("chaosplan", 500, smoke=True)
        det = d["determinism"]
        assert det["all_steps_fired"] is True
        assert det["counters_identical"] is True
        assert det["trace_signature_identical"] is True
        assert det["identical_fault_trace"] is True
        s = d["sweep"]
        assert s["write_boundaries"] >= 4
        assert s["crash_points"] >= s["write_boundaries"]
        assert s["recovered"] == s["crash_points"]
        assert s["all_recovered"] is True
        assert s["all_invariants_clean"] is True
        # every enumerated boundary was actually swept, each mode there
        modes = {(p["event"], p["mode"]) for p in s["points"]}
        assert ("wal_append", "before") in modes
        assert ("wal_append", "torn") in modes
        assert ("wal_append", "after") in modes
        assert ("ckpt_publish", "before") in modes
        assert ("ckpt_publish", "after") in modes
        c = d["combined"]
        assert c["registry_clients"] == 100_000
        assert len(c["cohort_client_ids"]) == c["clients"]
        assert c["reached_fold_target"] is True
        assert c["client_killed"] is True
        assert c["chaos_faults"] >= len(c["cohort_client_ids"])
        assert c["invariants_ok"] is True, c["invariants_violations"]
        for inv in ("exactly_once_folds", "version_monotone",
                    "no_reissued_seqs", "no_lost_unreported_folds"):
            assert inv in c["invariants_checked"]

    @pytest.mark.slow  # ~100s bench child; the fast gate runs the same
    # invocation once via ci/CI-script-smoke.sh's planet smoke block
    def test_planet_smoke_child_writes_valid_json(self):
        """The CI planet smoke invocation (100k registry, 1k cohort,
        3 rounds, CPU): the registry-backed population plane runs
        end-to-end through bench.py's planet phase child and emits the
        detail.planet contract keys — rounds completing at measured
        rounds/s, the warm-run RSS delta of a 10x-bigger registry
        within cohort-scale slack of the small one, two-tier edge-tree
        aggregation bit-identical to the flat fold of the same per-edge
        terms, and one jit trace per (bucket, nb) shape inside the pow2
        census budget."""
        d = self._run_child("planet", 500, smoke=True)
        assert d["registry_clients"] == 100_000
        assert d["registry_clients_small"] == 10_000
        assert d["cohort_size"] == 1_000
        assert d["rounds"] == 3
        assert d["edge_num"] >= 2
        assert d["rounds_per_sec"] > 0
        # flat-memory evidence: registry columns are ~17 bytes/client
        # and the warm-round RSS delta tracks the cohort, not the 10x
        # registry
        assert d["registry_bytes"] <= 32 * d["registry_clients"]
        assert d["rss_measured"] is True
        assert d["rss_scales_with_cohort"] is True
        assert d["planet_peak_rss_bytes"] > 0
        # two-tier tree == flat, bit for bit
        assert d["tree_identical_to_flat"] is True
        assert d["max_abs_diff_tree_vs_flat"] == 0.0
        # compile census: one trace per pow2 shape key, within budget
        assert d["one_trace_per_shape"] is True
        assert d["trace_within_budget"] is True
        assert d["trace_count"] <= d["trace_budget"]

    @pytest.mark.slow  # ~30s bench child; the fast gate runs the same
    # invocation once via ci/CI-script-smoke.sh's multichip smoke block
    def test_multichip_smoke_child_writes_valid_json(self):
        """The CI multichip smoke invocation (8 forced host devices,
        cohort 16, 3 rounds, CPU): the mesh-sharded federation runs
        end-to-end through bench.py's multichip phase child and emits
        the detail.multichip contract keys — rounds/s and clients/s
        per (data, fsdp) mesh shape, EVERY sharded shape's final
        params bitwise identical (max_abs_diff == 0.0) to the
        single-chip vmap world, one jit trace per shape, and the
        on-mesh streaming fold bitwise order-independent for raw and
        int8 uplinks (stream ≡ buffered preserved on the mesh; the
        zero-host-transfer half of the gate is `fedml-tpu audit --ci`
        over simulation.round_fn_mesh, run by the same CI script)."""
        d = self._run_child("multichip", 500, smoke=True)
        assert d["n_devices"] == 8
        assert d["cohort_size"] == 16
        assert d["rounds"] == 3
        assert set(d["shapes"]) == {"1x1", "8x1", "4x2", "2x4"}
        for key, entry in d["shapes"].items():
            assert entry["rounds_per_sec"] > 0
            assert entry["clients_per_sec"] > 0
            assert entry["trace_count"] == 1
            if key != "1x1":
                assert entry["max_abs_diff_vs_single_chip"] == 0.0
                assert entry["identical_to_single_chip"] is True
        assert d["one_trace_per_shape"] is True
        assert d["mesh_identical_to_single_chip"] is True
        assert d["max_abs_diff_stream_raw"] == 0.0
        assert d["max_abs_diff_stream_int8"] == 0.0
        assert d["agg_stream_raw_identical"] is True
        assert d["agg_stream_int8_identical"] is True
        assert "simulation.round_fn_mesh" in d["mesh_executables_registered"]

    @pytest.mark.slow  # ~15s bench child; the fast gate runs the same
    # invocation once via ci/CI-script-smoke.sh's elastic smoke block
    def test_elastic_smoke_child_writes_valid_json(self):
        """The CI elastic smoke invocation (8 forced host devices,
        cohort 16, 4 rounds, CPU): the elastic-mesh preemption seam
        runs end-to-end through bench.py's elastic phase child and
        emits the detail.elastic contract keys — a scripted
        maintenance notice at round 1 drains the round, lands the WAL
        ``preempt`` record write-ahead of a forced checkpoint and
        exits; the restart on 4 surviving devices restores
        device-direct onto the reshaped mesh, pairs the ``resume``
        record, and finishes **bitwise identical**
        (max_abs_diff == 0.0) to the uninterrupted 8-device run;
        accumulator limbs travel across the reshape identically for
        raw AND int8 uplinks; the InvariantChecker re-verifies the
        preempt/resume ledger; recovery_s is the headline."""
        d = self._run_child("elastic", 500, smoke=True)
        assert d["n_devices"] == 8
        assert d["devices_before"] == 8 and d["devices_after"] == 4
        assert d["cohort_size"] == 16 and d["rounds"] == 4
        assert d["preempted"] is True
        assert d["preempt_round"] == 1
        assert d["max_abs_diff_resume"] == 0.0
        assert d["resume_identical"] is True
        assert d["recovery_s"] > 0
        assert d["metric"] == "recovery_s" and d["value"] == d["recovery_s"]
        assert d["max_abs_diff_limbs_raw"] == 0.0
        assert d["max_abs_diff_limbs_int8"] == 0.0
        assert d["limb_travel_raw_identical"] is True
        assert d["limb_travel_int8_identical"] is True
        assert d["wal_kinds"] == ["preempt", "resume"]
        assert d["invariants_ok"] is True
        for inv in ("preempt_paired_with_checkpoint",
                    "preempt_resume_continuity"):
            assert inv in d["invariants_checked"]

    @pytest.mark.slow  # ~35s bench child; the fast gate runs the same
    # invocation once via ci/CI-script-smoke.sh's hier smoke block
    def test_hier_smoke_child_writes_valid_json(self):
        """The CI hier smoke invocation (3 clients/edge, edge_num ∈
        {1,2,4}, 3 rounds, CPU): the hierarchical server plane runs
        end-to-end through bench.py's hier phase child and emits the
        detail.hier contract keys — uploads/s scaling ≥2x from 1 to 4
        edges under the deliberately slow root link (the scheduled
        per-merge delay is the fixed per-round cost the edges
        amortize), tree-over-ranks bit-identical to the flat
        single-server world, and the mid-round edge kill/restart
        recovering bit-identically with the multi-tier invariant
        checker green on every world's artifacts."""
        d = self._run_child("hier", 500, smoke=True)
        assert set(d["edges"]) == {"1", "2", "4"}
        for e, entry in d["edges"].items():
            assert entry["clients"] == d["per_edge_clients"] * int(e)
            assert entry["uploads_folded"] == entry["clients"] * d["rounds"]
            assert entry["merges"] == int(e) * d["rounds"]
            assert entry["uploads_per_sec"] > 0
            assert entry["check_ok"] is True
        assert d["root_link_delay_s"] > 0
        # the acceptance gate: E merged limb-sets amortize the slow
        # root link over E x clients — ≥2x uploads/s at 4 edges vs 1
        assert d["uploads_scaling_e4_vs_e1"] >= 2.0
        assert d["hier_identical_to_flat"] is True
        assert d["hier_vs_flat_max_abs_diff"] == 0.0
        assert d["edge_kill_fired"] is True
        assert d["edge_kill_max_abs_diff"] == 0.0
        assert d["edge_kill_check_ok"] is True
        assert d["invariants_ok_all"] is True

    @pytest.mark.slow  # ~90s bench child; the fast gate runs the same
    # invocation once via ci/CI-script-smoke.sh's tracing smoke block
    def test_tracing_smoke_child_writes_valid_json(self):
        """The CI tracing smoke invocation (3 clients x 6 rounds, ABBA
        off/on worlds, CPU): the distributed-tracing layer runs
        end-to-end through bench.py's tracing phase child and emits the
        detail.tracing contract keys — every comm send span has a
        matched cross-process receive flow, the per-round critical-path
        segments sum to the measured round wall within 5%, the
        deterministically-attributed tracing overhead stays within the
        5% bound, aggregation results are bit-identical with tracing on
        vs telemetry off, and host-syncs-per-round is unchanged on the
        pipelined cohort."""
        d = self._run_child("tracing", 420, smoke=True)
        assert d["flow_starts"] > 0
        assert d["flows_matched"] == d["flow_starts"]
        assert d["all_flows_matched"] is True
        assert d["rounds_analyzed"] == d["rounds"]
        assert d["min_coverage"] >= 0.95
        assert d["segments_sum_within_5pct"] is True
        # the wall-clock delta is reported but inherently noisy on a
        # shared box; the gate is the deterministic attribution
        assert "overhead_pct" in d
        assert d["attributed_overhead_pct"] <= 5.0
        assert d["overhead_within_5pct"] is True
        assert d["params_match_off"] is True
        assert d["host_syncs_match"] is True
        assert all(1 <= r <= d["clients"] for r in d["straggler_ranks"])

    @pytest.mark.slow  # subprocess + 2-virtual-device mesh round
    def test_mesh_cpu_child_writes_valid_json(self):
        d = self._run_child("mesh", 300)
        assert d["mesh_shape"] == {"clients": 2}
        assert d["rounds_per_sec"] > 0
        # a --cpu mesh JSON must never read as a TPU number: the meta
        # block names the backend it ran on
        assert d["meta"]["backend"] == "cpu"

    @pytest.mark.slow  # ~10s bench child; the fast gate runs the same
    # invocation once via ci/CI-script-smoke.sh's crossdevice smoke block
    def test_crossdevice_smoke_child_writes_valid_json(self):
        """The CI crossdevice smoke invocation (100k registry, cohort
        64, 3 rounds, 30% scheduled mid-round vanish, CPU): the Beehive
        check-in plane runs end-to-end through bench.py's crossdevice
        phase child and emits the detail.crossdevice contract keys —
        every round closes on its fold target despite churn, the
        pairwise-masked fold is bitwise identical to the unmasked twin
        world (dropout recovery included), the WAL fold ledger matches
        the telemetry counters, exactly one jit trace per (speed tier,
        pow2 bucket), and the invariant checker plus `fedml-tpu check`
        stay green on the artifacts."""
        d = self._run_child("crossdevice", 500, smoke=True)
        assert d["registry_size"] == 100_000
        assert d["rounds"] == 3
        assert d["closes_on_target"] is True
        assert d["folds_per_s"] > 0
        assert d["mask_recoveries"] > 0
        assert d["masked_vs_unmasked_max_abs_diff"] == 0.0
        assert d["ledger_matches_counters"] is True
        assert d["one_trace_per_shape"] is True
        assert d["trace_count"] == len(d["shape_keys"])
        assert d["invariants_ok"] is True
        assert d["check_rc"] == 0
        assert d["counters"]["device_mask_recovery_failures_total"] == 0
        assert d["ok"] is True


class TestMetaBlock:
    """Every bench record carries the mandatory perf-plane meta block
    (`fedml-tpu perf --ratchet` groups by it): device_kind / backend /
    smoke labels plus the phase headline it compares. The phase child
    stamps it centrally in _phase_main."""

    def test_meta_headline_prefers_explicit_value(self):
        v, metric, unit = bench._meta_headline(
            {"value": 1.5, "metric": "rounds/s", "unit": "rounds/s",
             "rounds_per_sec": 9.9}
        )
        assert (v, metric, unit) == (1.5, "rounds/s", "rounds/s")

    def test_meta_headline_falls_back_to_throughput_keys(self):
        v, metric, unit = bench._meta_headline(
            {"rounds_per_sec": 2.5, "zzz": 1.0}
        )
        assert (v, metric) == (2.5, "rounds_per_sec")

    def test_meta_headline_deterministic_last_resort(self):
        # no headline, no known key: first numeric by sorted key — the
        # same record shape must always yield the same ratchet metric
        v, metric, _ = bench._meta_headline({"b_ms": 3.0, "a_ms": 7.0})
        assert (v, metric) == (7.0, "a_ms")
        assert bench._meta_headline({"note": "x"}) == (None, None, None)

    def test_find_mfu_recurses_and_ignores_bools(self):
        rec = {"detail": {"dense": [{"mfu_vs_bf16_peak": 0.031}]},
               "mfu_vs_bf16_peak_flag": True}
        assert bench._find_mfu(rec) == 0.031
        assert bench._find_mfu({"mfu_vs_bf16_peak": True}) is None

    def test_bench_meta_contract_keys(self):
        meta = bench._bench_meta("dense", True, {"rounds_per_sec": 2.0})
        assert meta["schema"] == 1
        assert meta["phase"] == "dense"
        assert meta["smoke"] is True
        # labels come from the live backend — on the CI box that is cpu
        assert meta["device_kind"]
        assert meta["backend"]
        assert meta["value"] == 2.0

    def test_phase_child_stamps_meta_centrally(self):
        # ONE stamping site, in the child's serializer — a new phase
        # cannot forget the contract
        import inspect

        src = inspect.getsource(bench._phase_main)
        assert "_bench_meta" in src
