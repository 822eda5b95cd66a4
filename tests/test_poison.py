"""data/poison.py attack synthesis + the loader's poisoned-world wiring.

tests/test_defense.py's poisoned worlds and docs/robustness.md's
threat model lean on these mechanisms being deterministic and correctly
labelled/triggered — a poison that silently no-ops would make every
"defended vs undefended" comparison vacuous.
"""

import logging

import numpy as np
import pytest

from fedml_tpu import constants
from fedml_tpu.data.poison import (
    POISON_TYPES,
    poison_clients,
    poison_dataset,
    stamp_trigger,
)

pytestmark = pytest.mark.smoke


def _images(n=40, seed=0, classes=10):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, 8, 8, 1).astype(np.float32)
    y = rng.randint(0, classes, n).astype(np.int64)
    return x, y


class TestPoisonDataset:
    def test_vocabulary_is_shared_with_constants(self):
        # one authoritative tuple: knob validation, the loader and this
        # module must agree
        assert POISON_TYPES == constants.POISON_TYPES
        assert set(POISON_TYPES) == {
            "label_flip", "targeted_flip", "backdoor_pattern", "edge_case",
        }

    def test_unknown_type_raises(self):
        x, y = _images()
        with pytest.raises(ValueError, match="poison_type"):
            poison_dataset(x, y, "flip", 10)

    def test_label_flip_rotates_labels_and_keeps_features(self):
        x, y = _images()
        px, py = poison_dataset(x, y, "label_flip", 10, fraction=1.0)
        np.testing.assert_array_equal(px, x)  # features untouched
        np.testing.assert_array_equal(py, (y + 1) % 10)
        assert not np.array_equal(py, y)

    def test_targeted_flip_moves_only_source_label(self):
        x, y = _images()
        px, py = poison_dataset(
            x, y, "targeted_flip", 10,
            source_label=3, target_label=7, fraction=1.0,
        )
        np.testing.assert_array_equal(px, x)
        was_source = y == 3
        assert (py[was_source] == 7).all()
        np.testing.assert_array_equal(py[~was_source], y[~was_source])

    def test_backdoor_stamps_trigger_and_relabels(self):
        x, y = _images()
        px, py = poison_dataset(
            x, y, "backdoor_pattern", 10,
            target_label=0, fraction=0.5, trigger_size=3,
        )
        # the chosen fraction is relabelled to the target AND carries
        # the bottom-right trigger patch at the stamp value (the max of
        # the stamped batch, hence also each stamped image's max)
        poisoned = np.where(
            np.any(px.reshape(len(px), -1) != x.reshape(len(x), -1), axis=1)
        )[0]
        assert len(poisoned) == max(1, int(0.5 * len(y)))
        for i in poisoned:
            assert py[i] == 0
            patch = px[i, -3:, -3:, :]
            assert (patch == px[i].max()).all()
        # untouched rows keep their labels and pixels
        untouched = sorted(set(range(len(y))) - set(poisoned.tolist()))
        np.testing.assert_array_equal(px[untouched], x[untouched])
        assert all(py[i] == y[i] or i in poisoned for i in untouched)

    def test_backdoor_needs_image_data(self):
        x = np.random.rand(10, 5).astype(np.float32)
        y = np.zeros(10, dtype=np.int64)
        with pytest.raises(ValueError, match="image"):
            poison_dataset(x, y, "backdoor_pattern", 10)

    def test_edge_case_falls_back_to_far_tail_noise_without_archive(
        self, tmp_path, caplog
    ):
        """No cached edge_case_examples archive -> synthetic far-tail
        rows claimed as the target class, with a log line saying so."""
        x, y = _images()
        with caplog.at_level(logging.INFO):
            px, py = poison_dataset(
                x, y, "edge_case", 10,
                target_label=2, fraction=0.5,
                data_cache_dir=str(tmp_path),  # empty: no archive
            )
        assert any("edge_case archive absent" in r.getMessage()
                   for r in caplog.records)
        changed = np.where(
            np.any(px.reshape(len(px), -1) != x.reshape(len(x), -1), axis=1)
        )[0]
        assert len(changed) == max(1, int(0.5 * len(y)))
        for i in changed:
            assert py[i] == 2
            # far-tail: mean ~3.0, way outside the clean [0, 1] range
            assert px[i].mean() > 1.5

    def test_fraction_math(self):
        x, y = _images(n=40)
        for frac, want in ((0.25, 10), (0.5, 20), (1.0, 40), (0.001, 1)):
            _, py = poison_dataset(x, y, "label_flip", 10, fraction=frac)
            assert (py != y).sum() == want, frac

    def test_deterministic_per_seed(self):
        x, y = _images()
        a = poison_dataset(x, y, "backdoor_pattern", 10, fraction=0.5, seed=4)
        b = poison_dataset(x, y, "backdoor_pattern", 10, fraction=0.5, seed=4)
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])
        c = poison_dataset(x, y, "backdoor_pattern", 10, fraction=0.5, seed=5)
        assert not np.array_equal(a[1], c[1])

    def test_inputs_never_mutated(self):
        x, y = _images()
        x0, y0 = x.copy(), y.copy()
        poison_dataset(x, y, "backdoor_pattern", 10, fraction=1.0)
        np.testing.assert_array_equal(x, x0)
        np.testing.assert_array_equal(y, y0)

    def test_stamp_trigger_patch_geometry(self):
        x = np.zeros((2, 8, 8, 3), dtype=np.float32)
        out = stamp_trigger(x, size=2, value=0.9)
        assert (out[:, -2:, -2:, :] == 0.9).all()
        assert (out[:, :-2, :, :] == 0.0).all()
        assert (out[:, :, :-2, :] == 0.0).all()


class TestPoisonClients:
    def test_poisons_listed_clients_only(self):
        xs, ys = zip(*[_images(seed=i) for i in range(4)])
        pxs, pys, idxs = poison_clients(
            list(xs), list(ys), "label_flip", 10, [1, 3], fraction=1.0
        )
        assert idxs == [1, 3]
        for i in (0, 2):
            np.testing.assert_array_equal(pys[i], ys[i])
        for i in (1, 3):
            np.testing.assert_array_equal(pys[i], (ys[i] + 1) % 10)

    def test_per_client_seeds_differ(self):
        """Two attackers with identical data must not poison the SAME
        sample subset (seed = 1000 + client idx)."""
        x, y = _images(n=40)
        xs, ys = [x.copy(), x.copy()], [y.copy(), y.copy()]
        pxs, pys, _ = poison_clients(
            xs, ys, "backdoor_pattern", 10, [0, 1], fraction=0.5
        )
        sel0 = np.any(pxs[0].reshape(40, -1) != x.reshape(40, -1), axis=1)
        sel1 = np.any(pxs[1].reshape(40, -1) != x.reshape(40, -1), axis=1)
        assert not np.array_equal(sel0, sel1)


class TestLoaderPoisonWiring:
    """args.poison_type wiring (docs/robustness.md threat model): the
    loader poisons attacker TRAIN shards after partitioning, before
    packing — every downstream view sees the attack; the test split
    stays clean."""

    def _load(self, args_factory, **kw):
        from fedml_tpu.data import load

        base = dict(
            dataset="mnist", synthetic_train_size=200,
            synthetic_test_size=40, client_num_in_total=4,
            client_num_per_round=4, batch_size=16,
            partition_method="homo",
        )
        base.update(kw)
        return load(args_factory(**base))

    def test_poisoned_clients_differ_clean_clients_match(self, args_factory):
        clean = self._load(args_factory)
        poisoned = self._load(
            args_factory, poison_type="label_flip",
            poisoned_client_idxs=[1],
        )
        y_clean = np.asarray(clean.packed_train.y)
        y_p = np.asarray(poisoned.packed_train.y)
        m = np.asarray(clean.packed_train.mask).astype(bool)
        # client 1 poisoned (labels rotated on real rows)...
        assert not np.array_equal(y_p[1][m[1]], y_clean[1][m[1]])
        # ...everyone else identical to the clean world
        for i in (0, 2, 3):
            np.testing.assert_array_equal(y_p[i][m[i]], y_clean[i][m[i]])
        # clean eval split untouched
        np.testing.assert_array_equal(
            np.asarray(poisoned.packed_test.y), np.asarray(clean.packed_test.y)
        )

    def test_fraction_draws_seeded_attackers(self, args_factory):
        a = self._load(
            args_factory, poison_type="label_flip",
            poisoned_client_fraction=0.5,
        )
        b = self._load(
            args_factory, poison_type="label_flip",
            poisoned_client_fraction=0.5,
        )
        np.testing.assert_array_equal(
            np.asarray(a.packed_train.y), np.asarray(b.packed_train.y)
        )

    def test_mixed_attack_list_pairs_with_idxs(self, args_factory):
        ds = self._load(
            args_factory,
            poison_type=["label_flip", "backdoor_pattern"],
            poisoned_client_idxs=[0, 2],
        )
        assert ds.client_num == 4  # loaded fine

    def test_attack_list_pairs_in_user_order(self, args_factory):
        """Regression: the idxs are NOT sorted/deduped behind the
        user's back — poison_type[k] lands on poisoned_client_idxs[k]
        even when the idxs are given out of order."""
        ds = self._load(
            args_factory,
            poison_type=["backdoor_pattern", "label_flip"],
            poisoned_client_idxs=[2, 0],  # backdoor->2, label_flip->0
            target_label=7,
        )
        clean = self._load(args_factory)
        m = np.asarray(clean.packed_train.mask).astype(bool)
        y_p = np.asarray(ds.packed_train.y)
        y_c = np.asarray(clean.packed_train.y)
        # client 2 got the backdoor: every real row relabelled to 7
        assert (y_p[2][m[2]] == 7).all()
        # client 0 got the label flip: rotation, not constant-7
        np.testing.assert_array_equal(y_p[0][m[0]], (y_c[0][m[0]] + 1) % 10)

    def test_duplicate_idxs_raise(self, args_factory):
        with pytest.raises(ValueError, match="duplicates"):
            self._load(
                args_factory, poison_type="label_flip",
                poisoned_client_idxs=[1, 1],
            )

    def test_attack_list_without_explicit_idxs_raises(self, args_factory):
        """A poison_type LIST zipped against a fraction-drawn (seed-
        dependent, sorted) attacker set would assign attacks to
        arbitrary clients silently — rejected at knob validation and in
        the loader."""
        with pytest.raises(ValueError, match="poisoned_client_idxs"):
            args_factory(
                poison_type=["label_flip", "backdoor_pattern"],
                poisoned_client_fraction=0.5,
            )
        a = args_factory()
        a.poison_type = ["label_flip", "backdoor_pattern"]
        a.poisoned_client_fraction = 0.5
        a.poisoned_client_idxs = None
        from fedml_tpu.data.loader import _maybe_poison_clients

        with pytest.raises(ValueError, match="poisoned_client_idxs"):
            _maybe_poison_clients(
                a, [np.zeros((4, 2))] * 4, [np.zeros(4, np.int32)] * 4,
                2, 0, "classification",
            )

    def test_mismatched_attack_list_raises(self, args_factory):
        with pytest.raises(ValueError, match="pair them"):
            self._load(
                args_factory,
                poison_type=["label_flip", "backdoor_pattern"],
                poisoned_client_idxs=[0],
            )

    def test_out_of_range_idx_raises(self, args_factory):
        with pytest.raises(ValueError, match="out of range"):
            self._load(
                args_factory, poison_type="label_flip",
                poisoned_client_idxs=[9],
            )

    def test_out_of_head_target_label_raises(self, args_factory):
        """target_label beyond class_num would one_hot to all-zero rows
        and train the attackers on garbage silently — reject loudly."""
        with pytest.raises(ValueError, match="target_label"):
            self._load(
                args_factory, poison_type="targeted_flip",
                poisoned_client_idxs=[0], target_label=10,
            )

    def test_poison_without_attackers_raises(self, args_factory):
        with pytest.raises(ValueError, match="no attacker"):
            self._load(args_factory, poison_type="label_flip")

    def test_unknown_poison_type_rejected_at_validation(self, args_factory):
        with pytest.raises(ValueError, match="unknown poison_type"):
            args_factory(poison_type="flipz", poisoned_client_idxs=[0])

    def test_vfl_party_csvs_reject_poison_loudly(
        self, tmp_path, args_factory
    ):
        """The VFL party-CSV early return must not silently ignore a
        configured poison (the attacks mutate horizontal per-client
        shards, which a vertical split does not have) — a run claiming
        a poisoned world must never train clean."""
        d = tmp_path / "nus_wide"
        d.mkdir(parents=True)
        (d / "party_0.csv").write_text("label,x0\n0,0.1\n1,0.2\n")
        with pytest.raises(ValueError, match="not supported for VFL"):
            self._load(
                args_factory,
                dataset="nus_wide",
                data_cache_dir=str(tmp_path),
                poison_type="label_flip",
                poisoned_client_idxs=[0],
            )
