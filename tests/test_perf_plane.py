"""The performance-attribution plane (ISSUE 18): core/devtime.py
device-time accounting, the analysis/perf.py roofline join + idle
ledger, and the bench-trajectory ratchet.

Oracle-style where it matters: the idle-gap test feeds a synthetic
timeline with KNOWN gaps through the same `attribute_idle` the live
cross-silo server calls; the roofline test hand-builds an audit report
and asserts the EXACT MFU arithmetic; the ratchet matrix plants a
regression and proves the gate trips (and never cross-compares CPU
smoke records against TPU records).
"""

import argparse
import json
import os

import pytest

from fedml_tpu.analysis import perf
from fedml_tpu.core import devtime
from fedml_tpu.core.telemetry import Telemetry

pytestmark = pytest.mark.smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="session")
def audit_report_path(tmp_path_factory):
    """The REAL static-cost report, generated here: audit_report.json
    is git-ignored, so a fresh checkout has none until somebody runs
    `fedml-tpu audit` — the tests that join against it must not depend
    on that (a lowering-only pass, seconds on the CPU)."""
    from fedml_tpu.analysis.audit import run_audit

    _findings, report = run_audit()
    path = tmp_path_factory.mktemp("audit") / "audit_report.json"
    path.write_text(json.dumps(report))
    return str(path)


# -- series-key parsing ------------------------------------------------


class TestSeriesKey:
    def test_tagged_series_round_trips(self):
        name, tags = perf.parse_series_key(
            "exec_device_seconds{bucket=b8,executable=simulation.round_fn}"
        )
        assert name == "exec_device_seconds"
        assert tags == {"bucket": "b8", "executable": "simulation.round_fn"}

    def test_untagged_series(self):
        assert perf.parse_series_key("round_wall_seconds") == (
            "round_wall_seconds", {}
        )


# -- idle-gap attribution oracle ---------------------------------------


class TestIdleOracle:
    def test_synthetic_timeline_yields_known_gaps(self):
        """t=100 broadcast, t=103 last arrival, aggregate takes 0.5s,
        round closes t=104 -> arrival_to_aggregate is exactly the
        0.5s the server sat on a full cohort before folding."""
        idle = perf.attribute_idle(
            now=104.0, bcast_t0=100.0, last_arrival=103.0,
            aggregate_s=0.5, prev_close=99.0,
        )
        assert idle["arrival_to_aggregate"] == pytest.approx(0.5)
        assert idle["close_to_broadcast"] == pytest.approx(1.0)

    def test_first_round_has_no_inter_round_gap(self):
        idle = perf.attribute_idle(
            now=10.0, bcast_t0=9.0, last_arrival=9.5, aggregate_s=0.1
        )
        assert "close_to_broadcast" not in idle

    def test_gaps_clamp_at_zero(self):
        # aggregation starting before the last arrival (streaming
        # folds) must not produce negative idle
        idle = perf.attribute_idle(
            now=10.0, bcast_t0=9.0, last_arrival=9.99,
            aggregate_s=5.0, prev_close=9.5,
        )
        assert idle["arrival_to_aggregate"] == 0.0
        assert idle["close_to_broadcast"] == 0.0

    def test_ledger_reconciles_to_wall(self):
        """segments + intra-round idle == wall -> recon_frac 1.0; the
        inter-round gap is excluded from intra-round reconciliation."""
        ledger = perf.summarize_ledger([
            {
                "round": 0,
                "wall_s": 2.0,
                "segments": {"broadcast_send": 0.2, "wait": 1.0,
                             "aggregate": 0.3},
                "idle": {"arrival_to_aggregate": 0.5},
                "wire_utilization_frac": 0.6,
            },
            {
                "round": 1,
                "wall_s": 1.0,
                "segments": {"broadcast_send": 0.1, "wait": 0.5,
                             "aggregate": 0.2},
                "idle": {"arrival_to_aggregate": 0.2,
                         "close_to_broadcast": 10.0},
                "wire_utilization_frac": 0.4,
            },
        ])
        assert ledger["rounds"][0]["recon_frac"] == 1.0
        assert ledger["rounds"][1]["recon_frac"] == 1.0
        assert ledger["total_wall_s"] == 3.0
        assert ledger["idle_totals_s"]["arrival_to_aggregate"] == 0.7
        assert ledger["idle_totals_s"]["close_to_broadcast"] == 10.0
        assert ledger["mean_wire_utilization_frac"] == 0.5

    def test_unaccounted_time_shows_as_low_recon(self):
        ledger = perf.summarize_ledger([
            {"round": 0, "wall_s": 2.0,
             "segments": {"aggregate": 0.5},
             "idle": {"arrival_to_aggregate": 0.5}},
        ])
        assert ledger["rounds"][0]["recon_frac"] == 0.5


# -- roofline join -----------------------------------------------------

# one executable whose arithmetic is trivially checkable by hand:
# 1000 calls x 2e9 FLOPs in 2.0 measured seconds = 1e12 FLOP/s; on a
# "TPU v5 lite" (197 TF/s bf16 peak) that is an MFU of 1/197.
_AUDIT = {
    "version": 1,
    "platform": "tpu",
    "executables": [
        {"executable": "simulation.round_fn", "case": "b8",
         "round_shaped": True, "hot": True,
         "flops": 2.0e9, "bytes_accessed": 1.0e9},
        {"executable": "simulation.round_fn", "case": "b32",
         "round_shaped": True, "hot": True,
         "flops": 8.0e9, "bytes_accessed": 2.0e9},
        {"executable": "agg.weighted_term", "case": None,
         "round_shaped": False, "hot": False,
         "flops": 36.0, "bytes_accessed": 72.0},
    ],
}


class TestRooflineJoin:
    def test_exact_mfu_arithmetic(self):
        measured = {
            ("simulation.round_fn", "b8"): {
                "count": 1000.0, "sum": 2.0, "min": 0.001, "max": 0.01,
            },
        }
        roof = perf.join_roofline(_AUDIT, measured, "TPU v5 lite")
        row = roof["rows"][0]
        assert row["joined"] is True and row["case_matched"] is True
        assert row["achieved_flops_per_sec"] == pytest.approx(1.0e12)
        peak = 197.0e12
        assert roof["peak_bf16_flops"] == pytest.approx(peak)
        # the report rounds MFU to 6 decimals
        assert row["mfu_vs_bf16_peak"] == round(1.0e12 / peak, 6)
        assert roof["coverage"] == 1.0

    def test_bucket_matches_audit_case_exactly(self):
        measured = {
            ("simulation.round_fn", "b32"): {
                "count": 10.0, "sum": 1.0, "min": 0.1, "max": 0.1,
            },
        }
        roof = perf.join_roofline(_AUDIT, measured, "TPU v5 lite")
        row = roof["rows"][0]
        assert row["case"] == "b32"
        assert row["flops_per_call"] == 8.0e9  # b32, not the b8 row

    def test_bound_verdict_from_arithmetic_intensity(self):
        # AI = 2e9/1e9 = 2 FLOP/byte, far below the v5 lite ridge
        # (197e12 / 0.819e12 ≈ 240) -> memory-bound
        measured = {
            ("simulation.round_fn", "b8"): {
                "count": 1.0, "sum": 1.0, "min": 1.0, "max": 1.0,
            },
        }
        roof = perf.join_roofline(_AUDIT, measured, "TPU v5 lite")
        assert roof["rows"][0]["bound"] == "memory"
        assert roof["ridge_flops_per_byte"] == pytest.approx(
            197.0 / 0.819, rel=1e-3
        )

    def test_unknown_executable_drags_coverage(self):
        measured = {
            ("simulation.round_fn", "b8"): {
                "count": 1.0, "sum": 3.0, "min": 3.0, "max": 3.0,
            },
            ("not.in.audit", ""): {
                "count": 1.0, "sum": 1.0, "min": 1.0, "max": 1.0,
            },
        }
        roof = perf.join_roofline(_AUDIT, measured, "TPU v5 lite")
        assert roof["coverage"] == 0.75  # 3 of 4 measured seconds joined
        assert roof["series_join_rate"] == 0.5

    def test_cpu_kind_reports_seconds_without_mfu(self):
        measured = {
            ("agg.weighted_term", ""): {
                "count": 4.0, "sum": 0.01, "min": 0.001, "max": 0.005,
            },
        }
        roof = perf.join_roofline(_AUDIT, measured, "cpu")
        assert roof["peak_bf16_flops"] is None
        assert "mfu_vs_bf16_peak" not in roof["rows"][0]
        assert roof["rows"][0]["joined"] is True

    def test_real_audit_report_joins(self, audit_report_path):
        """The REAL audit report: every registry executable the
        devtime plane instruments is joinable (the acceptance gate's
        coverage can reach 0.9 on an instrumented run)."""
        audit = perf.load_audit_report(audit_report_path)
        names = {r["executable"] for r in audit["executables"]}
        for exe in ("simulation.round_fn", "agg.weighted_term",
                    "agg.fold_tree", "serving.forward",
                    "planet.group_fn"):
            assert exe in names, exe
            measured = {(exe, ""): {"count": 2.0, "sum": 0.5,
                                    "min": 0.2, "max": 0.3}}
            roof = perf.join_roofline(audit, measured, "TPU v5 lite")
            assert roof["rows"][0]["joined"] is True, exe


# -- bench ratchet matrix ----------------------------------------------


def _bench_file(tmp_path, name, phase, kind, smoke, value,
                unit="rounds/s", omit_meta=False, crashed=False):
    rec = {"n": 1, "cmd": "bench", "rc": 0}
    if crashed:
        rec["parsed"] = None
    elif omit_meta:
        rec["parsed"] = {"metric": phase, "value": value, "unit": unit,
                         "detail": {}}
    else:
        rec["parsed"] = {
            "metric": phase, "value": value, "unit": unit, "detail": {},
            "meta": {"schema": 1, "phase": phase, "device_kind": kind,
                     "backend": "cpu" if kind == "cpu" else "tpu",
                     "smoke": smoke, "value": value, "metric": phase,
                     "unit": unit},
        }
    path = tmp_path / name
    path.write_text(json.dumps(rec))
    return str(path)


class TestRatchet:
    def test_planted_regression_fails(self, tmp_path):
        paths = [
            _bench_file(tmp_path, "BENCH_r01.json", "headline",
                        "TPU v5 lite", False, 1.14),
            _bench_file(tmp_path, "BENCH_r02.json", "headline",
                        "TPU v5 lite", False, 0.50),  # -56%: planted
        ]
        report = perf.run_ratchet(paths)
        assert report["regressions"] == 1
        assert report["ok"] is False
        g = report["groups"][0]
        assert g["verdict"] == "REGRESSION"
        assert g["best_prior"] == 1.14
        # the CLI exits 1 on exactly this report
        rc = perf.run_cli(argparse.Namespace(
            ratchet=paths, tolerance=perf.DEFAULT_TOLERANCE, quiet=True,
        ))
        assert rc == 1

    def test_improvement_and_jitter_pass(self, tmp_path):
        paths = [
            _bench_file(tmp_path, "BENCH_r01.json", "headline",
                        "TPU v5 lite", False, 1.00),
            _bench_file(tmp_path, "BENCH_r02.json", "headline",
                        "TPU v5 lite", False, 0.95),  # within 10%
            _bench_file(tmp_path, "BENCH_r03.json", "headline",
                        "TPU v5 lite", False, 1.30),  # improvement
        ]
        report = perf.run_ratchet(paths)
        assert report["ok"] is True
        assert report["groups"][0]["verdict"] == "ok"
        # best prior is the historical BEST, not the previous record
        assert report["groups"][0]["best_prior"] == 1.00

    def test_smoke_and_tpu_never_cross_compare(self, tmp_path):
        """A CPU smoke record 20x below the TPU capture is NOT a
        regression — the groups are disjoint by construction."""
        paths = [
            _bench_file(tmp_path, "BENCH_r01.json", "headline",
                        "TPU v5 lite", False, 1.14),
            _bench_file(tmp_path, "BENCH_r02.json", "headline",
                        "cpu", True, 0.05),
        ]
        report = perf.run_ratchet(paths)
        assert report["ok"] is True
        verdicts = {
            (g["phase"], g["device_kind"], g["smoke"]): g["verdict"]
            for g in report["groups"]
        }
        assert verdicts[("headline", "TPU v5 lite", False)] == "seeded"
        assert verdicts[("headline", "cpu", True)] == "seeded"

    def test_missing_meta_fails_loudly(self, tmp_path):
        paths = [
            _bench_file(tmp_path, "BENCH_r01.json", "headline",
                        "cpu", False, 1.0),
            _bench_file(tmp_path, "BENCH_r02.json", "headline",
                        "cpu", False, 1.0, omit_meta=True),
        ]
        report = perf.run_ratchet(paths)
        assert report["violations"], report
        assert report["ok"] is False
        rc = perf.run_cli(argparse.Namespace(
            ratchet=paths, tolerance=perf.DEFAULT_TOLERANCE, quiet=True,
        ))
        assert rc == 2

    def test_crashed_record_skipped_not_violated(self, tmp_path):
        paths = [
            _bench_file(tmp_path, "BENCH_r01.json", "headline",
                        "cpu", False, 1.0, crashed=True),
            _bench_file(tmp_path, "BENCH_r02.json", "headline",
                        "cpu", False, 1.0),
        ]
        report = perf.run_ratchet(paths)
        assert report["ok"] is True
        assert len(report["skipped"]) == 1
        assert "parsed=null" in report["skipped"][0]

    def test_latency_metrics_ratchet_downward(self, tmp_path):
        paths = [
            _bench_file(tmp_path, "BENCH_r01.json", "serving",
                        "cpu", False, 10.0, unit="p99_ms"),
            _bench_file(tmp_path, "BENCH_r02.json", "serving",
                        "cpu", False, 20.0, unit="p99_ms"),  # 2x slower
        ]
        report = perf.run_ratchet(paths)
        assert report["groups"][0]["verdict"] == "REGRESSION"
        # and an improvement (lower) passes
        paths[1] = _bench_file(tmp_path, "BENCH_r03.json", "serving",
                               "cpu", False, 5.0, unit="p99_ms")
        assert perf.run_ratchet(paths)["ok"] is True


# -- devtime measurement -----------------------------------------------


class TestDevtime:
    def test_telemetry_on_emits_histogram_spans_and_ring(self):
        tel = Telemetry.get_instance()
        tel.enabled = True
        with devtime.measure("simulation.round_fn", bucket="b8"):
            pass
        snap = tel.snapshot()
        key = ("exec_device_seconds"
               "{bucket=b8,executable=simulation.round_fn}")
        assert key in snap["histograms"]
        assert snap["histograms"][key]["count"] == 1
        names = [e.get("name") for e in tel.recorder.tail(10)]
        assert names.count("exec.simulation.round_fn") == 2  # B + E
        ring = devtime.ring_snapshot()
        assert len(ring) == 1
        assert ring[0]["executable"] == "simulation.round_fn"
        assert ring[0]["bucket"] == "b8"
        assert ring[0]["seconds"] >= 0.0

    def test_telemetry_off_still_records_the_fallback_ring(self):
        tel = Telemetry.get_instance()
        tel.enabled = False
        with devtime.measure("agg.fold_tree"):
            pass
        assert "exec_device_seconds" not in str(
            tel.snapshot()["histograms"]
        )
        ring = devtime.ring_snapshot()
        assert [e["executable"] for e in ring] == ["agg.fold_tree"]
        assert ring[0]["bucket"] is None
        assert devtime.measured_executables() == ["agg.fold_tree"]

    def test_ring_size_knob_adopted(self):
        ns = argparse.Namespace(devtime_ring_size=2)
        devtime.configure(ns)
        tel = Telemetry.get_instance()
        tel.enabled = False
        for i in range(5):
            with devtime.measure("agg.weighted_term", bucket=f"b{i}"):
                pass
        ring = devtime.ring_snapshot()
        assert len(ring) == 2  # bounded by the knob
        assert [e["bucket"] for e in ring] == ["b3", "b4"]  # newest kept

    def test_measure_reraises_but_always_accounts(self):
        tel = Telemetry.get_instance()
        tel.enabled = True
        with pytest.raises(RuntimeError):
            with devtime.measure("serving.forward", bucket="b4"):
                raise RuntimeError("dispatch failed")
        # the span closed and the time was still accounted
        assert len(devtime.ring_snapshot()) == 1
        key = "exec_device_seconds{bucket=b4,executable=serving.forward}"
        assert key in tel.snapshot()["histograms"]


# -- perf CLI over synthetic run artifacts ------------------------------


def _synth_run_dir(tmp_path):
    """A minimal telemetry_dir: one snapshot with exec histograms and
    one trace shard with two round.ledger instants."""
    hist_key = "exec_device_seconds{bucket=b8,executable=simulation.round_fn}"
    (tmp_path / "telemetry.jsonl").write_text(json.dumps({
        "kind": "telemetry_snapshot", "run_id": "t", "rank": 0,
        "histograms": {
            hist_key: {"count": 4, "sum": 2.0, "min": 0.4, "max": 0.6},
        },
    }) + "\n")
    events = [
        {"name": "round.ledger", "ph": "i", "ts": 1.0, "pid": 1,
         "args": {"round": r, "wall_s": 1.0,
                  "segments": {"broadcast_send": 0.2, "wait": 0.5,
                               "aggregate": 0.2},
                  "idle": {"arrival_to_aggregate": 0.1},
                  "wire_utilization_frac": 0.5}}
        for r in range(2)
    ]
    (tmp_path / "trace.json").write_text(
        json.dumps({"traceEvents": events, "otherData": {}})
    )
    return str(tmp_path)


def _report_ns(**kw):
    ns = argparse.Namespace(
        telemetry_dir=None, audit_report=None, device_kind=None,
        n_chips=1, min_coverage=perf.DEFAULT_MIN_COVERAGE, ratchet=None,
        tolerance=perf.DEFAULT_TOLERANCE, out=None, root=None, quiet=True,
    )
    for k, v in kw.items():
        setattr(ns, k, v)
    return ns


class TestPerfCli:
    def test_report_mode_emits_roofline_and_ledger(
        self, tmp_path, capsys, audit_report_path
    ):
        tdir = _synth_run_dir(tmp_path)
        rc = perf.run_cli(_report_ns(
            telemetry_dir=tdir, device_kind="TPU v5 lite", root=REPO,
            audit_report=audit_report_path,
        ))
        assert rc == 0
        report = json.load(open(os.path.join(tdir, "perf_report.json")))
        roof = report["roofline"]
        assert roof["coverage"] == 1.0
        assert roof["rows"][0]["executable"] == "simulation.round_fn"
        assert roof["rows"][0]["mfu_vs_bf16_peak"] is not None
        ledger = report["ledger"]
        assert len(ledger["rounds"]) == 2
        # the acceptance bar: accounted time reconciles within 5%
        assert all(r["recon_frac"] >= 0.95 for r in ledger["rounds"])
        assert ledger["mean_wire_utilization_frac"] == 0.5
        out = capsys.readouterr().out
        assert json.loads(out.strip().splitlines()[-1])["ok"] is True

    def test_low_coverage_fails_the_gate(self, tmp_path, audit_report_path):
        tdir = _synth_run_dir(tmp_path)
        # an unregistered executable dominating measured seconds
        hist_key = "exec_device_seconds{executable=rogue.exec}"
        with open(os.path.join(tdir, "telemetry.jsonl"), "a") as fh:
            fh.write(json.dumps({
                "kind": "telemetry_snapshot", "run_id": "t2", "rank": 0,
                "histograms": {
                    hist_key: {"count": 1, "sum": 98.0,
                               "min": 98.0, "max": 98.0},
                },
            }) + "\n")
        rc = perf.run_cli(_report_ns(
            telemetry_dir=tdir, device_kind="TPU v5 lite", root=REPO,
            audit_report=audit_report_path,
        ))
        assert rc == 1

    def test_missing_inputs_exit_2(self, tmp_path):
        assert perf.run_cli(_report_ns()) == 2
        assert perf.run_cli(_report_ns(
            telemetry_dir=str(tmp_path / "nope")
        )) == 2
        tdir = _synth_run_dir(tmp_path)
        assert perf.run_cli(_report_ns(
            telemetry_dir=tdir,
            audit_report=str(tmp_path / "no_audit.json"),
        )) == 2

    def test_cli_subcommand_is_wired(self):
        from fedml_tpu import cli

        parser = cli.build_parser()
        ns = parser.parse_args(["perf", "--ratchet", "x.json"])
        assert ns.ratchet == ["x.json"]
        assert callable(ns.fn)
