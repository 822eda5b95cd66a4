"""`fedml-tpu audit` — the compiled-artifact audit plane
(docs/static_analysis.md; fedml_tpu/analysis/compiled.py + audit.py).

Three layers, mirroring test_lint.py:

- **fixture executables**: one known-bad jit per rule (undonated
  round-shaped fn, claimed-donation-unmet, host callback, baked-in
  large constant, census overflow), asserting the exact rule id each
  checker reports from the LOWERED artifact — plus the matching
  known-good control;
- **ratchet**: audit findings ride the same count-keyed baseline
  machinery as lint — NEW fails, STALE fails, counts ratchet;
- **HEAD gate**: the repo's registered executables audit clean against
  the checked-in ``audit_baseline.json`` (in-process for the fast
  tier; the CLI subprocess end-to-end run carries the slow mark).

Everything here AOT-lowers only — no fixture executable is ever
called.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.analysis.audit import (
    AUDIT_BASELINE_NAME,
    AUDIT_RULES,
    RULE_CENSUS,
    RULE_CONSTANT,
    RULE_DONATION,
    RULE_HOST,
    audit_spec,
    run_audit,
)
from fedml_tpu.analysis.compiled import (
    AuditContext,
    AuditableSpec,
    LoweringCase,
    load_registry,
    lower_case,
    pow2_budget,
)
from fedml_tpu.analysis.engine import (
    diff_baseline,
    load_baseline,
    save_baseline,
)

pytestmark = pytest.mark.smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CTX = AuditContext()
FIXTURE_PATH = "tests/test_audit.py"


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _params():
    return {"w": _sds((4, 4)), "b": _sds((4,))}


def _spec(name, cases, **kw):
    return AuditableSpec(
        name=name, path=FIXTURE_PATH, provider=lambda ctx: list(cases), **kw
    )


def _rules(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------
# fixture executables, one per rule
# ---------------------------------------------------------------------


class TestDonationChecker:
    def test_round_shaped_without_aliasing_is_a_finding(self):
        def train_step(params, x):
            return jax.tree.map(lambda p: p + x.sum(), params)

        case = LoweringCase("b8", jax.jit(train_step), (_params(), _sds((8,))))
        findings, entries = audit_spec(
            _spec("fix.train_step", [case], round_shaped=True), CTX
        )
        assert _rules(findings) == [RULE_DONATION]
        assert entries[0]["aliased_inputs"] == 0

    def test_donated_round_shaped_is_clean(self):
        def train_step(params, x):
            return jax.tree.map(lambda p: p + x.sum(), params)

        case = LoweringCase(
            "b8",
            jax.jit(train_step, donate_argnums=(0,)),
            (_params(), _sds((8,))),
        )
        findings, entries = audit_spec(
            _spec("fix.train_step", [case], donate=(0,), round_shaped=True),
            CTX,
        )
        assert findings == []
        # both leaves of the donated tree alias outputs in the artifact
        assert entries[0]["aliased_inputs"] == 2
        assert entries[0]["claimed_donated_leaves"] == 2

    def test_claimed_donation_unmet_is_a_finding(self):
        """The docstring says donated, the jit call forgot — exactly
        the drift class the auditor exists for."""

        def train_step(params, x):
            return jax.tree.map(lambda p: p + x.sum(), params)

        case = LoweringCase("b8", jax.jit(train_step), (_params(), _sds((8,))))
        findings, _ = audit_spec(
            _spec("fix.train_step", [case], donate=(0,), round_shaped=True),
            CTX,
        )
        assert _rules(findings) == [RULE_DONATION]
        assert "donate_argnums=(0,)" in findings[0].message

    def test_partial_aliasing_is_a_finding(self):
        """A donated buffer whose shape matches no output cannot alias
        — the artifact proves the donation is (partly) wasted."""

        def train_step(params, x):
            # only 'w' survives; 'b'-shaped output does not exist, so
            # the donated 'b' buffer has nothing to alias into
            return {"w": params["w"] + x.sum()}

        case = LoweringCase(
            "b8",
            jax.jit(train_step, donate_argnums=(0,)),
            (_params(), _sds((8,))),
        )
        findings, entries = audit_spec(
            _spec("fix.train_step", [case], donate=(0,)), CTX
        )
        assert _rules(findings) == [RULE_DONATION]
        assert entries[0]["aliased_inputs"] == 1


class TestHostTransferChecker:
    def _callback_case(self):
        def fold(x):
            jax.debug.print("norm {}", x.sum())
            return x * 2.0

        return LoweringCase("b8", jax.jit(fold), (_sds((8,)),))

    def test_host_callback_in_hot_executable(self):
        findings, entries = audit_spec(
            _spec("fix.fold", [self._callback_case()], hot=True), CTX
        )
        assert _rules(findings) == [RULE_HOST]
        assert entries[0]["host_transfers"]  # the offending target named

    def test_cold_executable_may_call_back(self):
        findings, _ = audit_spec(
            _spec("fix.debug_fold", [self._callback_case()], hot=False), CTX
        )
        assert findings == []

    def test_pure_device_executable_is_clean(self):
        case = LoweringCase(
            "b8", jax.jit(lambda x: x @ x.T), (_sds((8, 8)),)
        )
        findings, entries = audit_spec(_spec("fix.mm", [case]), CTX)
        assert findings == []
        assert entries[0]["host_transfers"] == []


class TestConstantChecker:
    def test_large_baked_constant_is_a_finding(self):
        big = np.arange(32768, dtype=np.float32)  # 128 KiB closure blob

        def fold(x):
            return x + jnp.asarray(big)[: x.shape[0]]

        case = LoweringCase("b8", jax.jit(fold), (_sds((8,)),))
        findings, entries = audit_spec(_spec("fix.fold", [case]), CTX)
        assert _rules(findings) == [RULE_CONSTANT]
        assert entries[0]["max_constant_bytes"] == 32768 * 4

    def test_splat_constants_are_free(self):
        """A broadcasted fill (zeros/ones) is a compile-time splat —
        value-stable and cheap; only concrete closure blobs count."""

        def fold(x):
            return x + jnp.zeros((65536,), jnp.float32)[: x.shape[0]]

        case = LoweringCase("b8", jax.jit(fold), (_sds((8,)),))
        findings, entries = audit_spec(_spec("fix.fold", [case]), CTX)
        assert findings == []
        assert entries[0]["max_constant_bytes"] == 0

    def test_budget_is_per_spec(self):
        small = np.arange(64, dtype=np.float32)

        def fold(x):
            return x + jnp.asarray(small)[: x.shape[0]]

        case = LoweringCase("b8", jax.jit(fold), (_sds((8,)),))
        findings, _ = audit_spec(
            _spec("fix.fold", [case], constant_budget_bytes=16), CTX
        )
        assert _rules(findings) == [RULE_CONSTANT]


class TestCensusChecker:
    def test_overflowing_census_is_a_finding(self):
        fn = jax.jit(lambda x: x * 2.0)
        cases = [
            LoweringCase(f"b{b}", fn, (_sds((b,)),)) for b in (3, 5, 7)
        ]
        findings, _ = audit_spec(
            _spec("fix.fwd", cases, census_budget=2), CTX
        )
        assert RULE_CENSUS in _rules(findings)

    def test_callable_budget_and_pow2_span(self):
        assert pow2_budget((8, 512)) == 7
        assert pow2_budget((8, 32)) == 3
        fn = jax.jit(lambda x: x * 2.0)
        cases = [LoweringCase(f"b{b}", fn, (_sds((b,)),)) for b in (4, 8)]
        findings, _ = audit_spec(
            _spec(
                "fix.fwd", cases,
                census_budget=lambda ctx: pow2_budget((4, 8)),
            ),
            CTX,
        )
        assert findings == []


class TestStaticCost:
    def test_flops_and_bytes_reported(self):
        case = LoweringCase(
            "b16", jax.jit(lambda a, b: a @ b), (_sds((16, 16)), _sds((16, 16)))
        )
        _, entries = audit_spec(_spec("fix.mm", [case]), CTX)
        e = entries[0]
        assert e["flops"] and e["flops"] > 0
        assert e["bytes_accessed"] and e["bytes_accessed"] > 0
        assert e["arithmetic_intensity"] == e["flops"] / e["bytes_accessed"]

    def test_unjitted_fn_is_rejected(self):
        spec = _spec(
            "fix.raw", [LoweringCase("b8", lambda x: x, (_sds((8,)),))]
        )
        with pytest.raises(RuntimeError, match="lower"):
            audit_spec(spec, CTX)


# ---------------------------------------------------------------------
# baseline ratchet (shared engine machinery, audit findings)
# ---------------------------------------------------------------------


class TestAuditBaseline:
    def _findings(self):
        def train_step(params, x):
            return jax.tree.map(lambda p: p + x.sum(), params)

        case = LoweringCase("b8", jax.jit(train_step), (_params(), _sds((8,))))
        findings, _ = audit_spec(
            _spec("fix.train_step", [case], round_shaped=True), CTX
        )
        return findings

    def test_new_finding_fails_and_baselined_passes(self):
        findings = self._findings()
        new, stale = diff_baseline(findings, {})
        assert len(new) == 1 and not stale
        baseline = {findings[0].key(): 1}
        new, stale = diff_baseline(findings, baseline)
        assert not new and not stale

    def test_stale_entry_fails(self):
        findings = self._findings()
        baseline = {findings[0].key(): 1, "gone:aot-donation:fixed": 1}
        new, stale = diff_baseline(findings, baseline)
        assert not new
        assert stale == ["gone:aot-donation:fixed"]

    def test_count_ratchet(self):
        findings = self._findings() * 2  # same key twice (two cases)
        baseline = {findings[0].key(): 1}
        new, stale = diff_baseline(findings, baseline)
        assert len(new) == 1  # the second occurrence is NEW

    def test_save_and_load_roundtrip(self, tmp_path):
        findings = self._findings()
        path = str(tmp_path / AUDIT_BASELINE_NAME)
        save_baseline(path, findings, comment="audit fixture ledger")
        loaded = load_baseline(path)
        assert loaded == {findings[0].key(): 1}
        assert json.load(open(path))["comment"] == "audit fixture ledger"


# ---------------------------------------------------------------------
# the repo at HEAD
# ---------------------------------------------------------------------


class TestRepoAtHead:
    def test_registry_covers_the_hot_planes(self):
        reg = load_registry()
        assert {
            "simulation.round_fn",
            "simulation.round_fn_ragged",
            "simulation.round_fn_mesh",
            "planet.group_fn",
            "serving.forward",
            "agg.fold_tree",
            "agg.weighted_term",
            "agg.weighted_term_clipped",
            "agg.weighted_delta_term_clipped",
        } <= set(reg)
        # the round/fold/group executables CLAIM donation; the auditor
        # holds them to it (test below proves the claims verify)
        assert reg["simulation.round_fn"].donate == (0, 1)
        assert reg["simulation.round_fn_ragged"].donate == (0, 1)
        assert reg["simulation.round_fn_mesh"].donate == (0, 1)
        assert reg["planet.group_fn"].donate == (0,)
        assert reg["agg.fold_tree"].donate == (0,)

    def test_audit_baseline_is_empty(self):
        """The donation burn-down is COMPLETE: planet.group_fn's
        per-group rebind donates its carry, so the ledger holds zero
        accepted TODOs. The ratchet therefore fails on ANY new
        compile-time contract violation — nothing is grandfathered."""
        baseline = load_baseline(os.path.join(REPO, AUDIT_BASELINE_NAME))
        assert baseline == {}

    def test_repo_audits_clean_against_checked_in_baseline(self):
        """Every registered executable lowers; donation verified (or
        explicitly baselined), zero unbaselined host transfers, census
        within budget — the `fedml-tpu audit --ci` contract,
        in-process."""
        findings, report = run_audit()
        baseline = load_baseline(os.path.join(REPO, AUDIT_BASELINE_NAME))
        new, stale = diff_baseline(findings, baseline)
        assert new == [], [f.render() for f in new]
        assert stale == []
        assert all(f.rule in AUDIT_RULES for f in findings)
        # the report carries the roofline denominators: per-case static
        # FLOPs/bytes for every lowered executable, nothing executed
        by_name = {}
        for e in report["executables"]:
            by_name.setdefault(e["executable"], []).append(e)
        for name in ("simulation.round_fn", "simulation.round_fn_ragged"):
            assert len(by_name[name]) == len(AuditContext().cohort_buckets)
        for e in report["executables"]:
            assert e["flops"] is not None and e["flops"] > 0
            assert e["bytes_accessed"] is not None
        # donation PROVEN on the round/fold/mesh/group executables —
        # the baseline is EMPTY, nothing donation-shaped is
        # grandfathered anymore
        for e in (
            by_name["simulation.round_fn"]
            + by_name["simulation.round_fn_ragged"]
            + by_name["simulation.round_fn_mesh"]
            + by_name["planet.group_fn"]
            + by_name["agg.fold_tree"]
        ):
            assert e["aliased_inputs"] >= e["claimed_donated_leaves"] > 0
        # hot executables are host-transfer-free across the census
        assert all(not e["host_transfers"] for e in report["executables"])
        assert report["roofline"]

    def test_only_subset_and_unknown_name(self):
        findings, report = run_audit(only=["agg.weighted_term"])
        assert [e["executable"] for e in report["executables"]] == [
            "agg.weighted_term"
        ]
        assert findings == []
        with pytest.raises(KeyError, match="unknown auditable"):
            run_audit(only=["nope.missing"])

    def test_only_subset_ratchets_against_filtered_baseline(self):
        """--only runs ratchet against the subset's (now empty) ledger
        slice: the once-baselined planet.group_fn donates its per-group
        rebind since the mesh refactor, so both a formerly-TODO'd and a
        finding-free executable exit clean, and neither run misreads
        the other's (absent) entries as stale."""
        from fedml_tpu.analysis.audit import main

        assert main(["--only", "planet.group_fn"]) == 0
        assert main(["--only", "agg.weighted_term"]) == 0
        assert main(["--only", "simulation.round_fn_mesh"]) == 0
        assert main(["--only", "simulation.round_fn_ragged"]) == 0

    @pytest.mark.slow  # subprocess pays interpreter + jax startup
    def test_cli_audit_ci_exits_zero_at_head(self, tmp_path):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        report = tmp_path / "audit_report.json"
        proc = subprocess.run(
            [
                sys.executable, "-m", "fedml_tpu.cli", "audit", "--ci",
                "--json", "--report", str(report),
            ],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        assert out["ok"] is True
        assert out["new"] == [] and out["stale"] == []
        data = json.loads(report.read_text())
        assert data["executables"] and data["roofline"]

    @pytest.mark.slow
    def test_cli_rejects_update_baseline_in_ci_and_with_only(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        for extra in (["--ci"], ["--only", "agg.weighted_term"]):
            proc = subprocess.run(
                [
                    sys.executable, "-m", "fedml_tpu.cli", "audit",
                    "--update-baseline", *extra,
                ],
                cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
            )
            assert proc.returncode == 2, (extra, proc.stderr)

    def test_lower_case_never_executes(self):
        """The audit's core promise: lowering only. A fn that would
        FAIL LOUDLY if executed (python-side assert on concrete data)
        still lowers fine, because tracing never materializes values."""
        calls = []

        def fwd(x):
            calls.append(1)  # trace-time only
            return x * 2.0

        spec = _spec("fix.fwd", [LoweringCase("b8", jax.jit(fwd), (_sds((8,)),))])
        _, entries = audit_spec(spec, CTX)
        assert len(calls) == 1  # traced exactly once, never run
        assert entries[0]["flops"] is not None
