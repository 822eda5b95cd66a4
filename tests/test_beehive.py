"""Cross-device Beehive plane (fedml_tpu/cross_device/, docs/cross_device.md).

Covers the ISSUE-16 acceptance contract:
- pairwise-mask algebra: masks cancel bitwise in the mod-p fold, the
  masked world's final params are BITWISE identical to an unmasked
  world under the same churn schedule (raw and through the int8 offer
  codec), and Shamir dropout recovery restores exact cancellation when
  maskers vanish mid-round;
- churn is normal: rounds close on their fold target (never cohort
  completeness) within the report window, with a window close when the
  target is unreachable, and stragglers fold async FedBuff-style with
  oracle-checked staleness discounts;
- the ledger discipline: at-most-once fold (dedup counted), no fold
  without a ledgered check-in, WAL fold counts == telemetry counters,
  and a planted bad Shamir share is flagged by the InvariantChecker
  (pubkey verification), never silently folded;
- device-class compile buckets: one jit trace per (speed tier, pow2
  bucket), asserted over a heterogeneous cohort;
- the `fedml-tpu device` CLI smoke seam.
"""

import json
import tempfile

import numpy as np
import pytest

pytestmark = pytest.mark.smoke

import fedml_tpu
from fedml_tpu.core import secure_agg as sa
from fedml_tpu.core.chaos import reset_chaos
from fedml_tpu.core.invariants import InvariantChecker
from fedml_tpu.core.telemetry import Telemetry
from fedml_tpu.cross_device import run_beehive_world
from fedml_tpu.cross_device.protocol import (
    decode_offer_params,
    encode_offer_params,
    flat_dim,
    linear_template,
    pack_participants,
    pack_reveals,
    unpack_participants,
    unpack_reveals,
)
from fedml_tpu.scale.registry import ClientRegistry

from tests.conftest import make_args


REG_SIZE = 2_000
COHORT = 16
P = sa.FIELD_PRIME


def beehive_args(**kw):
    kw.setdefault("training_type", "simulation")
    kw.setdefault("client_registry_size", REG_SIZE)
    kw.setdefault("crossdevice_cohort", COHORT)
    kw.setdefault("comm_round", 2)
    kw.setdefault("telemetry_dir", tempfile.mkdtemp(prefix="beehive_td_"))
    kw.setdefault("checkpoint_dir", tempfile.mkdtemp(prefix="beehive_ck_"))
    kw.setdefault("run_id", f"beehive-{abs(hash(tuple(sorted(kw)))) % 10**8}")
    a = make_args(**kw)
    fedml_tpu.init(a)
    return a


def run_world(**kw):
    a = beehive_args(**kw)
    Telemetry.reset()
    reset_chaos()
    out = run_beehive_world(a, feature_dim=8, class_num=4)
    out["args"] = a
    return out


def vanish_schedule(rounds, frac=0.3, fault=None):
    """Schedule ``frac`` of each round's (precomputed) cohort to vanish
    at upload time."""
    reg = ClientRegistry(REG_SIZE, seed=0, duty_hours=14)
    steps = []
    for r in range(rounds):
        ids = reg.sample_available_cohort(r, COHORT)
        k = max(1, int(frac * len(ids)))
        for d in ids[:k]:
            steps.append(
                {
                    "at": {
                        "event": "device.upload",
                        "device": int(d),
                        "round": r,
                    },
                    "fault": dict(fault or {"kind": "vanish"}),
                }
            )
    return steps


class TestMaskAlgebra:
    """The secure-agg primitives, independent of the protocol."""

    def test_pairwise_masks_cancel_bitwise_over_full_set(self):
        rng = np.random.default_rng(0)
        ids = [3, 11, 42, 99]
        secrets = {i: sa.derive_mask_secret(i * 7 + 1, 0) for i in ids}
        pubs = {i: sa.mask_public_key(secrets[i]) for i in ids}
        dim = 24
        qs = {
            i: rng.integers(0, P, size=dim, dtype=np.int64) for i in ids
        }
        masked_sum = np.zeros(dim, dtype=np.int64)
        plain_sum = np.zeros(dim, dtype=np.int64)
        for i in ids:
            m = sa.pairwise_mask_vector(i, secrets[i], pubs, dim)
            masked_sum = np.mod(masked_sum + qs[i] + m, P)
            plain_sum = np.mod(plain_sum + qs[i], P)
        assert np.array_equal(masked_sum, plain_sum)

    def test_dropout_residue_equals_unmask_correction(self):
        ids = [1, 5, 8, 13, 21]
        secrets = {i: sa.derive_mask_secret(i * 31 + 5, 2) for i in ids}
        pubs = {i: sa.mask_public_key(secrets[i]) for i in ids}
        dim = 10
        vanished = 8
        folded = [i for i in ids if i != vanished]
        acc = np.zeros(dim, dtype=np.int64)
        for i in folded:
            acc = np.mod(
                acc + sa.pairwise_mask_vector(i, secrets[i], pubs, dim), P
            )
        # the folded masks' residue is exactly the vanished device's
        # dangling pairwise terms...
        corr = sa.unmask_correction(
            vanished, secrets[vanished],
            {i: pubs[i] for i in folded}, dim,
        )
        # ...minus the terms among the folded themselves (which cancel)
        assert np.array_equal(np.mod(acc - corr, P), np.zeros(dim))

    def test_shamir_recovers_mask_secret_and_poison_breaks_pubkey(self):
        secret = sa.derive_mask_secret(12345, 7)
        pub = sa.mask_public_key(secret)
        rng = np.random.default_rng(3)
        shares = sa.shamir_share(np.int64(secret), 5, 2, rng)
        back = int(sa.shamir_reconstruct(shares[:3], [1, 2, 3]))
        assert back == secret
        assert sa.mask_public_key(back) == pub
        # poison every revealed share by +1: Lagrange weights sum to 1,
        # so the reconstruction is secret+1 — and the pubkey catches it
        bad = int(
            sa.shamir_reconstruct(np.mod(shares[:3] + 1, P), [1, 2, 3])
        )
        assert bad == (secret + 1) % P
        assert sa.mask_public_key(bad) != pub


class TestProtocolCodecs:
    def test_offer_codec_is_deterministic_and_int8(self):
        params = linear_template(6, 3)
        params["w"] = params["w"] + np.float32(0.25)
        enc = encode_offer_params(params)
        assert enc["w"]["q"].dtype == np.int8
        dec1 = decode_offer_params(enc)
        dec2 = decode_offer_params(encode_offer_params(params))
        for k in ("b", "w"):
            assert np.array_equal(dec1[k], dec2[k])
        assert flat_dim(6, 3) == 6 * 3 + 3

    def test_participants_and_reveals_round_trip(self):
        roster = {42: 7, 3: 99, 17: 1}
        packed = pack_participants(roster)
        assert list(packed["ids"]) == [3, 17, 42]  # sorted is normative
        assert unpack_participants(packed) == roster
        reveals = {8: [(1, 100), (3, 200)], 2: [(2, 50)]}
        assert unpack_reveals(pack_reveals(reveals)) == reveals


class TestBeehiveWorld:
    def test_clean_world_closes_every_round_on_target(self):
        out = run_world(comm_round=3)
        recs = out["round_records"]
        assert len(recs) == 3
        for rec in recs:
            assert rec["close_reason"] == "target"
            assert rec["folds"] >= rec["fold_target"]
        tel = Telemetry.get_instance()
        assert tel.get_counter("device_uploads_folded_total") == sum(
            r["folds"] for r in recs
        )
        rep = InvariantChecker(
            telemetry_dir=out["args"].telemetry_dir,
            checkpoint_dir=out["args"].checkpoint_dir,
        ).check()
        assert rep.ok, rep.to_dict()
        assert "device_masked_folds_balance" in rep.to_dict()["checked"]

    def test_masked_equals_unmasked_bitwise_under_churn(self):
        steps = vanish_schedule(rounds=3)
        m = run_world(comm_round=3, chaos_schedule=steps)
        assert any(r["recovered"] > 0 for r in m["round_records"])
        tel = Telemetry.get_instance()
        assert tel.get_counter("device_mask_recovery_failures_total") == 0.0
        rep = InvariantChecker(
            telemetry_dir=m["args"].telemetry_dir,
            checkpoint_dir=m["args"].checkpoint_dir,
        ).check()
        assert rep.ok, rep.to_dict()
        assert "device_mask_recovery_verified" in rep.checked
        u = run_world(
            comm_round=3, chaos_schedule=steps, crossdevice_secure_agg=False
        )
        assert all(r["recovered"] == 0 for r in u["round_records"])
        assert np.array_equal(m["final_flat"], u["final_flat"])
        assert float(
            np.max(np.abs(m["final_flat"] - u["final_flat"]))
        ) == 0.0

    def test_churn_rounds_still_close_on_target(self):
        steps = vanish_schedule(rounds=2, frac=0.3)
        out = run_world(comm_round=2, chaos_schedule=steps)
        for rec in out["round_records"]:
            assert rec["close_reason"] == "target"
            assert rec["folds"] >= rec["fold_target"]

    def test_unreachable_target_closes_on_window_not_stall(self):
        # fold target = 100% of the roster, but one device vanishes:
        # the target is unreachable, so the report window must close
        # the round (churn != stall)
        steps = vanish_schedule(rounds=1, frac=0.05)
        out = run_world(
            comm_round=1,
            chaos_schedule=steps,
            crossdevice_fold_target_frac=1.0,
        )
        rec = out["round_records"][0]
        assert rec["close_reason"] == "window"
        assert rec["folds"] < rec["fold_target"]
        tel = Telemetry.get_instance()
        assert (
            tel.get_counter("device_rounds_closed_total", reason="window")
            == 1.0
        )

    def test_late_upload_folds_with_staleness_discount(self):
        # an after_close vanish delivers its (already-masked) upload
        # after the round closed; it must fold into the NEXT round's
        # finalize as FedBuff food, not be dropped
        steps = vanish_schedule(
            rounds=1, frac=0.2, fault={"kind": "vanish", "after_close": True}
        )
        out = run_world(comm_round=2, chaos_schedule=steps)
        recs = out["round_records"]
        assert recs[0]["late_folded"] == 0
        assert recs[1]["late_folded"] >= 1
        tel = Telemetry.get_instance()
        assert tel.get_counter("device_uploads_late_total") >= 1.0

    def test_bad_share_world_is_flagged_by_checker(self):
        reg = ClientRegistry(REG_SIZE, seed=0, duty_hours=14)
        ids = reg.sample_available_cohort(0, COHORT)
        steps = [
            {
                "at": {
                    "event": "device.upload",
                    "device": int(ids[0]),
                    "round": 0,
                },
                "fault": {"kind": "vanish"},
            }
        ] + [
            {
                "at": {
                    "event": "device.upload",
                    "device": int(d),
                    "round": 0,
                },
                "fault": {"kind": "bad_share"},
            }
            for d in ids[1:]
        ]
        out = run_world(comm_round=1, chaos_schedule=steps)
        tel = Telemetry.get_instance()
        assert tel.get_counter("device_mask_recovery_failures_total") >= 1.0
        rep = InvariantChecker(
            telemetry_dir=out["args"].telemetry_dir,
            checkpoint_dir=out["args"].checkpoint_dir,
        ).check()
        assert not rep.ok
        assert any(
            v["invariant"] == "device_mask_recovery_verified"
            for v in rep.to_dict()["violations"]
        )

    def test_one_trace_per_tier_bucket(self):
        out = run_world(comm_round=3)
        assert out["trace_count"] == len(out["shape_keys"])
        reg = ClientRegistry(REG_SIZE, seed=0, duty_hours=14)
        tiers = {int(t) for t in reg.speed_tier}
        assert {k[0] for k in out["shape_keys"]} <= tiers

    def test_fold_ledger_in_wal_matches_counters_and_checkins(self):
        from fedml_tpu.core.checkpoint import RoundWAL

        steps = vanish_schedule(rounds=2)
        out = run_world(comm_round=2, chaos_schedule=steps)
        recs = [
            r
            for r in RoundWAL(out["args"].checkpoint_dir).records()
            if r.get("kind") == "crossdevice"
        ]
        assert len(recs) == 2
        tel = Telemetry.get_instance()
        assert tel.get_counter("device_uploads_folded_total") == sum(
            len(r["folded"]) for r in recs
        )
        for r in recs:
            assert set(r["folded"]) <= set(r["checkins"])
            assert set(r["checkins"]) <= set(r["cohort"])
            # masked-folds balance, re-added by hand
            ups = sum(int(v) for v in r["upload_checksums"].values())
            corrs = sum(int(v) for v in r["correction_checksums"].values())
            assert int(r["field_checksum"]) == (ups - corrs) % P


class TestKnobValidation:
    def test_named_errors(self):
        with pytest.raises(ValueError, match="crossdevice_fold_target_frac"):
            make_args(crossdevice_fold_target_frac=0.0)
        with pytest.raises(ValueError, match="crossdevice_fold_target_frac"):
            make_args(crossdevice_fold_target_frac=1.5)
        with pytest.raises(ValueError, match="crossdevice_report_window_s"):
            make_args(crossdevice_report_window_s=-1)
        with pytest.raises(ValueError, match="crossdevice_quant_scale"):
            make_args(crossdevice_quant_scale=0)
        with pytest.raises(ValueError, match="crossdevice_mask_threshold"):
            make_args(crossdevice_mask_threshold=0)
        with pytest.raises(ValueError, match="crossdevice_duty_hours"):
            make_args(crossdevice_duty_hours=25)
        with pytest.raises(ValueError, match="crossdevice_cohort"):
            make_args(crossdevice_cohort="nope")

    def test_defaults_validate(self):
        a = make_args()
        assert a.crossdevice_fold_target_frac == 0.6
        assert a.crossdevice_secure_agg is True
        assert a.crossdevice_mask_threshold == 2


class TestDeviceCli:
    def test_dry_run_prints_status_json(self, capsys):
        from fedml_tpu.cli import main as cli_main

        rc = cli_main(["device", "--dry-run"])
        assert rc == 0
        status = json.loads(capsys.readouterr().out.strip())
        assert status["plane"] == "crossdevice"
        assert status["registry_size"] > 0
        assert status["secure_agg"] is True
        assert status["update_dim"] == flat_dim(8, 4)