"""Byzantine-robustness layer (docs/robustness.md threat model):
streamable defenses, the anomaly screen, and quarantine end to end.

Pins the PR's guarantees in isolation:

- ``norm_diff_clipping`` / ``weak_dp`` ride the streaming fold —
  per-upload clipped terms are bitwise order-independent, equivalent to
  the stacked ``RobustAggregator`` math, and the buffered close folds
  the SAME executables (stream == buffered bit-identity with a defense
  on, ``agg_stream_fallback_total`` staying 0);
- weak-DP noise is drawn from a run-seed + round derived key at
  finalize — never the seed's fixed ``PRNGKey(0)`` footgun;
- unknown defense strings fail LOUDLY at every entry point;
- the ``AnomalyScreen`` reputation/quarantine lifecycle: score ->
  EWMA -> quarantine -> probation -> fresh slate, staleness-aware;
- the cross-silo managers route a quarantined rank through the
  drop-expected path (no stall) and exclude it from cohorts.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fedml_tpu
from fedml_tpu import models
from fedml_tpu.core.aggregation import (
    RobustAggregator,
    StreamingAccumulator,
    derive_defense_rng,
    needs_full_cohort,
    normalize_weights,
    stack_pytrees,
)
from fedml_tpu.core.defense import AnomalyScreen, anomaly_score
from fedml_tpu.core.telemetry import Telemetry
from fedml_tpu.data import load


def _trees(n=5, seed=0, scale_spread=True):
    rng = np.random.RandomState(seed)
    trees, ws = [], []
    for _ in range(n):
        s = 10.0 ** rng.randint(-3, 3) if scale_spread else 1.0
        trees.append(
            {
                "k": jnp.asarray(rng.randn(17, 7).astype(np.float32) * s),
                "b": jnp.asarray(rng.randn(7).astype(np.float32)),
            }
        )
        ws.append(float(rng.randint(1, 200)))
    return trees, ws


@pytest.mark.smoke
class TestClippedStreamingFold:
    def test_clipped_fold_is_bitwise_order_independent(self):
        trees, ws = _trees()
        g = trees[0]

        def run(order):
            acc = StreamingAccumulator(g)
            for i in order:
                acc.fold_clipped(trees[i], g, 2.5, ws[i])
            return acc.finalize()

        ref = run(range(len(trees)))
        rng = np.random.RandomState(3)
        for _ in range(6):
            out = run(rng.permutation(len(trees)).tolist())
            jax.tree.map(
                lambda a, b: np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b)
                ),
                ref, out,
            )

    def test_clipped_fold_matches_stacked_robust_aggregator(self, args_factory):
        """The streamed per-term clip must compute the SAME math as the
        reference-parity stacked path (clip_updates + weighted_average)
        — the satellite contract that narrowing needs_full_cohort did
        not change semantics."""
        trees, ws = _trees(scale_spread=False)
        g = trees[0]
        bound = 1.5
        acc = StreamingAccumulator(g)
        clipped_flags = []
        for t, w in zip(trees, ws):
            norm, clipped = acc.fold_clipped(t, g, bound, w)
            clipped_flags.append(clipped)
            assert norm >= 0.0
        got = acc.finalize()

        a = args_factory(defense_type="norm_diff_clipping", norm_bound=bound)
        robust = RobustAggregator(a)
        stacked = stack_pytrees(trees)
        weights = normalize_weights(jnp.asarray(ws))
        want = robust.aggregate(stacked, weights, g)
        jax.tree.map(
            lambda x, y: np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=2e-5, atol=1e-6
            ),
            got, want,
        )
        # the zero delta (trees[0] == g) must never read as clipped
        assert clipped_flags[0] is False
        assert any(clipped_flags[1:])

    def test_delta_clip_geometry(self):
        """Async currency: the clipped delta term is w * delta *
        min(1, bound/||delta||) — staleness weight never changes the
        clip radius."""
        delta = {"k": jnp.full((4,), 3.0)}  # ||delta|| = 6
        acc = StreamingAccumulator(delta)
        norm, clipped = acc.fold_delta_clipped(delta, 1.5, 10.0)
        assert clipped is True
        np.testing.assert_allclose(norm, 6.0, rtol=1e-6)
        out = acc.finalize()  # weighted mean of one term = clipped delta
        np.testing.assert_allclose(
            np.asarray(out["k"]), 3.0 * (1.5 / 6.0), rtol=1e-6
        )

    def test_encoded_clipped_fold_matches_raw(self, args_factory):
        """int8-encoded uploads clip to (allclose) the same result the
        raw path produces — decode + clip + weight in one executable."""
        from fedml_tpu.core.compression import Int8Codec

        codec = Int8Codec()
        trees, ws = _trees(scale_spread=False)
        g = trees[0]
        raw = StreamingAccumulator(g)
        enc = StreamingAccumulator(g)
        for t, w in zip(trees[1:], ws[1:]):
            delta = jax.tree.map(lambda a, b: a - b, t, g)
            payload = codec.encode(delta)
            decoded_t = jax.tree.map(
                lambda gg, d: gg + d, g, codec.decode(payload)
            )
            raw.fold_clipped(decoded_t, g, 1.0, w)
            enc.fold_encoded_clipped(codec, payload, g, 1.0, w)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6
            ),
            raw.finalize(), enc.finalize(),
        )


@pytest.mark.smoke
class TestWeakDPRng:
    def test_aggregate_requires_rng_for_weak_dp(self, args_factory):
        a = args_factory(defense_type="weak_dp")
        robust = RobustAggregator(a)
        trees, ws = _trees(n=3, scale_spread=False)
        stacked = stack_pytrees(trees)
        weights = normalize_weights(jnp.asarray(ws[:3]))
        with pytest.raises(ValueError, match="derive_defense_rng"):
            robust.aggregate(stacked, weights, trees[0], rng=None)

    def test_derived_keys_differ_per_round_and_seed(self):
        k0 = derive_defense_rng(0, 0)
        k1 = derive_defense_rng(0, 1)
        k0b = derive_defense_rng(1, 0)
        assert not np.array_equal(np.asarray(k0), np.asarray(k1))
        assert not np.array_equal(np.asarray(k0), np.asarray(k0b))
        # deterministic per (seed, round): the stream==buffered noise
        # bit-identity depends on it
        np.testing.assert_array_equal(
            np.asarray(k1), np.asarray(derive_defense_rng(0, 1))
        )

    def test_noise_differs_across_rounds(self, args_factory):
        a = args_factory(defense_type="weak_dp", stddev=0.1)
        robust = RobustAggregator(a)
        params = {"k": jnp.zeros((8, 8))}
        n0 = robust.add_noise(params, derive_defense_rng(0, 0))
        n1 = robust.add_noise(params, derive_defense_rng(0, 1))
        assert not np.array_equal(np.asarray(n0["k"]), np.asarray(n1["k"]))


@pytest.mark.smoke
class TestDefenseValidation:
    def test_unknown_defense_rejected_everywhere(self, args_factory):
        # knob validation
        with pytest.raises(ValueError, match="unknown defense_type"):
            args_factory(defense_type="norm_clip")
        # RobustAggregator construction (the seed silently fell through
        # to a plain mean here)
        a = args_factory()
        a.defense_type = "typo"
        with pytest.raises(ValueError, match="unknown defense_type"):
            RobustAggregator(a)
        with pytest.raises(ValueError, match="unknown defense_type"):
            needs_full_cohort(a, None)

    def test_needs_full_cohort_narrowed_to_median(self, args_factory):
        a = args_factory()
        for streamable in ("norm_diff_clipping", "weak_dp"):
            a.defense_type = streamable
            assert needs_full_cohort(a, None) is None
        a.defense_type = "median"
        assert "median" in needs_full_cohort(a, None)

    def test_bounds_validated(self, args_factory):
        with pytest.raises(ValueError, match="norm_bound"):
            args_factory(defense_type="norm_diff_clipping", norm_bound=0.0)
        # a YAML `norm_bound: null` names the knob, not a bare TypeError
        with pytest.raises(ValueError, match="norm_bound=None"):
            args_factory(defense_type="norm_diff_clipping", norm_bound=None)
        with pytest.raises(ValueError, match="stddev"):
            args_factory(defense_type="weak_dp", stddev=-1.0)
        with pytest.raises(ValueError, match="defense_anomaly_threshold"):
            args_factory(defense_anomaly_threshold=-0.1)
        with pytest.raises(ValueError, match="defense_quarantine_rounds"):
            args_factory(defense_quarantine_rounds=0)
        # a YAML `defense_quarantine_rounds: null` names the knob too
        with pytest.raises(ValueError, match="defense_quarantine_rounds=None"):
            args_factory(defense_quarantine_rounds=None)


@pytest.mark.smoke
class TestAnomalyScreen:
    def _screen(self, args_factory, threshold=0.5, rounds=2):
        return AnomalyScreen(
            args_factory(
                defense_anomaly_threshold=threshold,
                defense_quarantine_rounds=rounds,
            )
        )

    def test_disabled_by_default(self, args_factory):
        assert AnomalyScreen(args_factory()).enabled is False
        assert self._screen(args_factory).enabled is True

    def test_score_oracle(self):
        # neutral: no reference norm, no cosine
        assert anomaly_score(5.0, None, None) == 0.0
        # pure norm excess: 3x the reference -> 0.5 * (3 - 1) = 1.0
        assert anomaly_score(3.0, None, 1.0) == pytest.approx(1.0)
        # ratio cap at 4: score saturates at 1.5
        assert anomaly_score(100.0, None, 1.0) == pytest.approx(1.5)
        # anti-aligned at reference norm: 0.5 * 1 * (1-(-1))/2 = 0.5
        assert anomaly_score(1.0, -1.0, 1.0) == pytest.approx(0.5)
        # harm weighting: the same anti-alignment at a TENTH of the
        # reference norm carries a tenth of the cosine evidence
        assert anomaly_score(0.1, -1.0, 1.0) == pytest.approx(0.05)
        # perfectly aligned, reference-sized: clean
        assert anomaly_score(1.0, 1.0, 1.0) == 0.0

    def test_reputation_ewma_and_trip(self, args_factory):
        s = self._screen(args_factory, threshold=0.5)
        assert s.observe(0, 0.4, 1.0) is False  # rep 0.16
        assert s.reputation(0) == pytest.approx(0.4 * 0.4)
        assert s.observe(0, 2.0, 1.0) is True  # rep 0.896 >= 0.5
        assert s.is_quarantined(0)
        assert s.quarantines_total == 1
        # fresh slate after the trip
        assert s.reputation(0) == 0.0

    def test_quarantine_lifecycle(self, args_factory):
        s = self._screen(args_factory, threshold=0.5, rounds=2)
        s.observe(3, 5.0, 1.0)
        assert s.quarantined_indexes() == [3]
        # the tick closing the TRIPPING period does not count as served
        # probation: the rank sits out exactly 2 full periods
        assert s.tick() == []
        assert s.tick() == []  # period 1 of 2 served
        assert s.is_quarantined(3)
        assert s.tick() == [3]  # period 2 served: released
        assert not s.is_quarantined(3)
        assert s.quarantined_indexes() == []

    def test_quarantine_rounds_one_excludes_one_cohort(self, args_factory):
        """Regression: probation of 1 must exclude the rank from ONE
        subsequent cohort, not zero (the tripping round's own close
        used to consume the whole probation)."""
        s = self._screen(args_factory, threshold=0.5, rounds=1)
        s.observe(0, 5.0, 1.0)
        assert s.tick() == []  # the tripping round's close
        assert s.is_quarantined(0)  # still out for the next cohort
        assert s.tick() == [0]

    def test_staleness_normalizes_norm_evidence(self, args_factory):
        """An update 3 publishes stale spans ~4 publishes of movement:
        its norm is divided by (1 + staleness) before the excess test,
        so a stale honest catch-up never reads as an attack."""
        s = self._screen(args_factory)
        delta = {"k": jnp.full((4,), 2.0)}  # ||.|| = 4
        for _ in range(4):
            _, n, _ = s.score_upload(delta)
            s.observe(9, 0.0, n)  # window median = 4
        fresh_score, fresh_norm, _ = s.score_upload(
            {"k": jnp.full((4,), 8.0)}  # ||.|| = 16: 4x the median
        )
        stale_score, stale_norm, _ = s.score_upload(
            {"k": jnp.full((4,), 8.0)}, staleness=3
        )
        assert fresh_norm == pytest.approx(16.0)
        assert stale_norm == pytest.approx(4.0)  # /(1+3)
        assert fresh_score > 1.0
        assert stale_score == 0.0

    def test_first_upload_of_window_is_cosine_neutral(self, args_factory):
        s = self._screen(args_factory)
        score, norm, cos = s.score_upload({"k": jnp.ones((3,))})
        assert cos is None and score == 0.0 and norm > 0

    def test_converged_cohort_does_not_self_quarantine(self, args_factory):
        """Regression: once a federation converges, accepted norms
        collapse toward zero — a ratio against a near-zero median read
        ANY ordinary step as a 4x anomaly and mass-quarantined honest
        ranks (measured in an async poisoned world). The reference norm
        floors at a fraction of the clip radius: deltas far below the
        clip bound can never be norm-anomalous."""
        s = AnomalyScreen(
            args_factory(
                defense_type="norm_diff_clipping", norm_bound=1.0,
                defense_anomaly_threshold=0.35,
            )
        )
        # converged cohort: tiny accepted norms fill the window
        for _ in range(8):
            s.observe(0, 0.0, 0.001)
        assert s._ref_norm == pytest.approx(0.25)  # floored, not 0.001
        # an ordinary small step (well under the clip radius) is clean
        score, norm, _ = s.score_upload({"k": jnp.asarray([0.1, 0.1])})
        assert norm < 0.25
        assert score == 0.0
        # a clip-radius-scale delta against the converged cohort still
        # reads as the anomaly it is
        big, bn, _ = s.score_upload({"k": jnp.asarray([0.8, 0.8])})
        assert bn > 1.0 and big > 0.35

    def test_screen_only_floor_adapts_without_clip_radius(
        self, args_factory
    ):
        """Screening with no clipping defense configured
        (defense_type=None is legal — the screen enables on the
        threshold alone) must not anchor its floor on the unused
        norm_bound knob: honest deltas of norm ~0.1 against the default
        norm_bound=5.0 floor (1.25) would leave the norm-excess signal
        dead. Without a clip radius the floor tracks the peak window
        median instead."""
        s = AnomalyScreen(
            args_factory(defense_anomaly_threshold=0.35)
        )
        assert s.norm_floor is None  # no clip radius to anchor on
        # honest cohort at norm ~0.1 fills the window
        for _ in range(8):
            s.observe(0, 0.0, 0.1)
        assert s._ref_norm == pytest.approx(0.1)
        # an attacker shipping 10x the honest norm saturates the ratio
        # cap — the norm-excess signal must be ALIVE at this scale
        score, norm, _ = s.score_upload({"k": jnp.asarray([1.0])})
        assert norm == pytest.approx(1.0)
        assert score > 1.0
        # converged collapse: the floor holds at a quarter of the peak
        # median, so ordinary post-convergence steps stay clean
        for _ in range(16):
            s.observe(0, 0.0, 0.001)
        assert s._ref_norm == pytest.approx(0.025)
        small, _, _ = s.score_upload({"k": jnp.asarray([0.002])})
        assert small == 0.0


def _mk_world_args(make, run_id, rank, n=4, rounds=2, **kw):
    base = dict(
        training_type="cross_silo", backend="LOCAL", dataset="mnist",
        synthetic_train_size=240, synthetic_test_size=40, model="lr",
        partition_method="homo", client_num_in_total=n,
        client_num_per_round=n, comm_round=rounds, epochs=1,
        batch_size=16, learning_rate=0.1, frequency_of_the_test=rounds,
        shuffle=False, run_id=run_id,
    )
    base.update(kw)
    a = make(**base)
    a.rank = rank
    return a


def _build_node(make, run_id, rank, **kw):
    a = _mk_world_args(make, run_id, rank, **kw)
    a = fedml_tpu.init(a)
    ds = load(a)
    m = models.create(a, ds.class_num)
    return a, ds, m


@pytest.mark.smoke
class TestAggregatorDefenseUnit:
    def _agg(self, args_factory, **kw):
        from fedml_tpu.cross_silo.horizontal.fedml_aggregator import (
            FedMLAggregator,
        )

        Telemetry.reset()
        a, ds, m = _build_node(args_factory, "defagg", 0, **kw)
        return FedMLAggregator(a, m)

    def test_clipping_streams_without_fallback(self, args_factory):
        agg = self._agg(
            args_factory, agg_mode="stream",
            defense_type="norm_diff_clipping", norm_bound=0.5,
        )
        assert agg.streaming  # no buffered fallback for clipping
        tel = Telemetry.get_instance()
        assert sum(
            tel.counters_matching("agg_stream_fallback_total").values()
        ) == 0
        g = agg.global_params
        far = jax.tree.map(lambda x: x + 3.0, g)
        agg.begin_round([0, 1])
        assert agg.receive_upload(0, 10.0, model_params=far) == "folded"
        assert agg.defense_clipped == 1
        assert sum(
            tel.counters_matching("defense_clipped_total").values()
        ) == 1
        # duplicate still deduped
        assert agg.receive_upload(0, 10.0, model_params=far) == "duplicate"

    def test_buffered_mode_cosine_evidence_engages(self, args_factory):
        """Buffered mode has no accumulator until close, so the screen's
        cosine reference is the screening-only running delta sum — an
        anti-aligned upload must accrue cosine evidence there exactly
        like it does on the streaming path (the defense-support table
        promises the full screen in every mode)."""
        agg = self._agg(
            args_factory, agg_mode="buffered",
            defense_anomaly_threshold=0.45,
        )
        assert not agg.streaming
        g = agg.global_params
        up = jax.tree.map(lambda x: x + 1.0, g)
        anti = jax.tree.map(lambda x: x - 1.0, g)
        agg.begin_round([0, 1])
        assert agg.receive_upload(0, 10.0, model_params=up) == "buffered"
        assert agg.receive_upload(1, 10.0, model_params=anti) == "buffered"
        # same norm as the reference (norm evidence 0) but cos = -1
        # against the running sum: score 0.5, reputation 0.4 * 0.5
        assert agg.screen.reputation(1) == pytest.approx(0.2, abs=0.02)
        agg.aggregate()
        assert agg._screen_ref is None  # reference resets per window

    def test_async_accepts_streamable_defense_rejects_median(
        self, args_factory
    ):
        """The construction-time rejection is lifted for clipping and
        weak_dp; median still cannot stream."""
        agg = self._agg(
            args_factory, agg_mode="async",
            defense_type="norm_diff_clipping",
        )
        assert agg.streaming
        with pytest.raises(ValueError, match="agg_mode=async"):
            self._agg(args_factory, agg_mode="async", defense_type="median")

    def test_screen_quarantines_and_rejects_before_fold(self, args_factory):
        agg = self._agg(
            args_factory, agg_mode="stream",
            defense_type="norm_diff_clipping", norm_bound=5.0,
            defense_anomaly_threshold=0.4, defense_quarantine_rounds=1,
        )
        g = agg.global_params
        near = jax.tree.map(lambda x: x + 0.01, g)
        agg.begin_round([0, 1, 2])
        assert agg.receive_upload(0, 10.0, model_params=near) == "folded"
        assert agg.receive_upload(1, 10.0, model_params=near) == "folded"
        # attacker: huge anti-aligned delta vs the running aggregate
        attack = jax.tree.map(lambda x: x - 50.0, g)
        assert agg.receive_upload(2, 10.0, model_params=attack) == "quarantined"
        assert agg.quarantined_ranks() == {3}
        assert agg.defense_rejected == 1
        # rejected upload never folded
        assert agg.num_received() == 2
        tel = Telemetry.get_instance()
        assert sum(
            tel.counters_matching("defense_quarantined_total").values()
        ) == 1
        # while quarantined, further uploads are rejected outright
        assert agg.receive_upload(2, 10.0, model_params=near) == "quarantined"
        # the tripping round's close doesn't count; the NEXT tick
        # releases with a fresh slate
        assert agg.tick_defense() == []
        assert agg.tick_defense() == [2]
        assert agg.quarantined_ranks() == set()

    def test_weak_dp_noise_applied_at_finalize_deterministically(
        self, args_factory
    ):
        """Streaming weak_dp == clip-in-fold + noise keyed by (seed,
        round): two identical aggregators produce identical bits."""
        outs = []
        for _ in range(2):
            agg = self._agg(
                args_factory, agg_mode="stream",
                defense_type="weak_dp", norm_bound=1.0, stddev=0.05,
            )
            g = agg.global_params
            up = jax.tree.map(lambda x: x + 0.5, g)
            agg.begin_round([0])
            agg.receive_upload(0, 10.0, model_params=up)
            outs.append(agg.aggregate())
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            outs[0], outs[1],
        )
        # and the noise is actually THERE: the clipped mean without
        # noise differs
        agg = self._agg(
            args_factory, agg_mode="stream",
            defense_type="norm_diff_clipping", norm_bound=1.0,
        )
        g = agg.global_params
        up = jax.tree.map(lambda x: x + 0.5, g)
        agg.begin_round([0])
        agg.receive_upload(0, 10.0, model_params=up)
        no_noise = agg.aggregate()
        assert any(
            not np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(
                jax.tree.leaves(outs[0]), jax.tree.leaves(no_noise)
            )
        )


def _run_world(make, run_id, n=4, **kw):
    """One LOCAL world to its end; the server and its evaluation of the
    final model on the clean test split."""
    from fedml_tpu.cross_silo import Client, Server

    Telemetry.reset()
    a0, ds0, m0 = _build_node(make, run_id, 0, n=n, **kw)
    server = Server(a0, None, ds0, m0)
    clients = []
    for r in range(1, n + 1):
        a, ds, m = _build_node(make, run_id, r, n=n, **kw)
        clients.append(Client(a, None, ds, m))
    threads = [threading.Thread(target=c.run, daemon=True) for c in clients]
    for t in threads:
        t.start()
    server.run()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    stats = server.aggregator.test_on_server_for_all_clients(a0.comm_round)
    return server, stats


def _quarantined_ranks(tel):
    # defense_quarantined_total{rank=N}
    return {
        int(key.rsplit("rank=", 1)[1].rstrip("}"))
        for key in tel.counters_matching("defense_quarantined_total")
    }


@pytest.mark.smoke
class TestDefendedWorlds:
    def test_stream_equals_buffered_with_weak_dp(self, args_factory):
        """Bit-identity extends to weak_dp: per-term clip + finalize
        noise from the derived key are shared by both modes."""
        kw = dict(defense_type="weak_dp", norm_bound=1.0, stddev=0.01)
        buffered, _ = _run_world(
            args_factory, "wdp_buf", agg_mode="buffered", **kw
        )
        streamed, _ = _run_world(
            args_factory, "wdp_str", agg_mode="stream", **kw
        )
        assert buffered.aggregator.peak_buffered == 4
        assert streamed.aggregator.peak_buffered == 0
        assert streamed.aggregator.defense_clipped > 0
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            buffered.aggregator.get_global_model_params(),
            streamed.aggregator.get_global_model_params(),
        )

    def test_poisoned_world_diverges_and_the_defended_one_recovers(
        self, args_factory, tmp_path
    ):
        """Two of six silos train on poisoned shards (one label flip,
        one backdoor pattern; data/poison.py). Undefended, the server's
        loss on the clean test split blows up; with clipping and the
        anomaly screen both attackers are quarantined, every round
        still completes, the model lands back near the clean run, and
        every aggregated upload is exactly one fold."""
        from fedml_tpu.core.invariants import InvariantChecker

        n, rounds = 6, 6
        size = dict(
            n=n, rounds=rounds, synthetic_train_size=360,
            synthetic_test_size=120,
        )
        poison = dict(
            poison_type=["label_flip", "backdoor_pattern"],
            poisoned_client_idxs=[1, 4], poison_sample_fraction=1.0,
        )
        attackers = {2, 5}  # silo idx + 1
        defense = dict(
            defense_type="norm_diff_clipping", norm_bound=1.0,
            defense_anomaly_threshold=0.35, defense_quarantine_rounds=3,
        )
        _, clean_stats = _run_world(args_factory, "pw_clean", **size)
        _, undef_stats = _run_world(
            args_factory, "pw_undef", **size, **poison
        )
        assert undef_stats["loss"] > 3.0 * clean_stats["loss"]

        ck, td = str(tmp_path / "ck"), str(tmp_path / "td")
        defended, def_stats = _run_world(
            args_factory, "pw_def", **size, **poison, **defense,
            checkpoint_dir=ck, checkpoint_freq=1, telemetry_dir=td,
        )
        tel = Telemetry.get_instance()

        def total(counter):
            return sum(tel.counters_matching(counter).values())

        assert attackers <= _quarantined_ranks(tel)
        assert defended.manager.round_idx == rounds
        # the screen scores uploads as they arrive, so the defended loss
        # is one of a few values (0.021, 0.025 seen) against 0.208
        assert def_stats["loss"] < 0.5 * undef_stats["loss"]
        assert total("defense_clipped_total") > 0
        assert total("defense_quarantined_rejected_total") >= 1
        folds = total("agg_folds_total")
        assert folds == total("cross_silo_clients_aggregated_total")
        assert folds <= n * rounds
        report = InvariantChecker(telemetry_dir=td, checkpoint_dir=ck).check()
        assert report.ok, report.to_dict()

    def test_async_finishes_when_only_quarantined_ranks_remain(
        self, args_factory
    ):
        """Liveness: honest clients leave an elastic async federation
        after the Byzantine rank is quarantined. Folds are the only
        progress signal and the survivor can never fold — the server
        must finish loudly instead of hanging forever."""
        from fedml_tpu.cross_silo import Client, Server

        Telemetry.reset()
        kw = dict(
            n=3, rounds=50,  # fold target unreachable: 150 folds
            agg_mode="async", async_publish_every=1,
            elastic_membership=True,
            defense_type="norm_diff_clipping", norm_bound=1.0,
            defense_anomaly_threshold=0.2, defense_quarantine_rounds=500,
        )
        a0, ds0, m0 = _build_node(args_factory, "aqstall", 0, **kw)
        server = Server(a0, None, ds0, m0)
        clients = []
        for r in range(1, 4):
            a, ds, m = _build_node(args_factory, "aqstall", r, **kw)
            clients.append(Client(a, None, ds, m))
        # rank 3 is Byzantine: enormous garbage deltas, quarantined
        # within its first couple of uploads and never released
        byz = clients[2].trainer
        byz_orig = byz.train

        def byzantine_train(params, round_idx):
            new_params, n = byz_orig(params, round_idx)
            return jax.tree.map(lambda x: x + 1000.0, new_params), n

        byz.train = byzantine_train
        # honest ranks 1..2 leave after a few dispatch cycles
        for c in clients[:2]:
            mgr = c.manager
            orig_tas = mgr._train_and_send
            counter = {"n": 0}

            def tas(msg, mgr=mgr, orig=orig_tas, counter=counter):
                counter["n"] += 1
                if counter["n"] > 4:
                    mgr.leave()
                    return
                orig(msg)

            mgr._train_and_send = tas
        threads = [
            threading.Thread(target=c.run, daemon=True) for c in clients
        ]
        for t in threads:
            t.start()
        server_thread = threading.Thread(target=server.run, daemon=True)
        server_thread.start()
        server_thread.join(timeout=90)
        assert not server_thread.is_alive(), (
            "async server hung with only quarantined ranks online"
        )
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
        mgr = server.manager
        assert mgr.aggregator.quarantined_ranks() == {3}
        assert mgr.async_folds < mgr._async_target_folds()  # stall finish

    def test_quarantined_rank_cannot_stall_quorum_round(self, args_factory):
        """A rank quarantined MID-ROUND drops through the drop-expected
        path: the round completes without waiting on it, later
        broadcasts exclude it, and the federation finishes. The
        attacker here is maximally Byzantine — it ships garbage params
        every round (model-replacement style), which the screen trips
        on within a round or two regardless of arrival order."""
        from fedml_tpu.cross_silo import Client, Server

        Telemetry.reset()
        kw = dict(
            n=4, rounds=3,
            defense_type="norm_diff_clipping", norm_bound=1.0,
            defense_anomaly_threshold=0.3, defense_quarantine_rounds=5,
        )
        a0, ds0, m0 = _build_node(args_factory, "qworld", 0, **kw)
        server = Server(a0, None, ds0, m0)
        clients = []
        for r in range(1, 5):
            a, ds, m = _build_node(args_factory, "qworld", r, **kw)
            clients.append(Client(a, None, ds, m))
        # rank 2 is Byzantine: model-replacement uploads, far off-cone
        attacker = clients[1].trainer
        orig_train = attacker.train

        def byzantine_train(params, round_idx):
            new_params, n = orig_train(params, round_idx)
            return jax.tree.map(lambda x: x - 100.0, new_params), n

        attacker.train = byzantine_train
        threads = [
            threading.Thread(target=c.run, daemon=True) for c in clients
        ]
        for t in threads:
            t.start()
        server.run()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        assert server.manager.round_idx == 3  # every round completed
        tel = Telemetry.get_instance()
        q = tel.counters_matching("defense_quarantined_total")
        assert "defense_quarantined_total{rank=2}" in q  # the attacker
        # the attacker stays quarantined (probation 5 > rounds): the
        # later rounds ran over the 3 honest survivors only
        assert server.aggregator.quarantined_ranks() == {2}
        # and at least one rejected upload was counted
        assert sum(
            tel.counters_matching(
                "defense_quarantined_rejected_total"
            ).values()
        ) >= 1
