"""On-device synthetic stand-ins (loader._device_synth_classification)
and mixed-precision dtype propagation (models.spec.ensure_float).

Why these exist: the machine with the chip has no dataset, and features
need not cross the host link, so stand-in federations are generated in
device memory (only labels/masks cross the link); and a blanket ``astype(float32)`` at a model's entry silently
promotes every conv back to f32 under bf16 compute — both were found
benching on the real chip.
"""

import numpy as np
import pytest

import fedml_tpu
from fedml_tpu import models
from fedml_tpu.arguments import Arguments
from fedml_tpu.data import load
from tests.conftest import make_args

pytestmark = pytest.mark.smoke


def _args(**over):
    base = dict(
        dataset="femnist",
        synthetic_train_size=400,
        synthetic_test_size=100,
        model="cnn",
        partition_method="hetero",
        partition_alpha=0.5,
        client_num_in_total=4,
        client_num_per_round=4,
        comm_round=1,
        epochs=1,
        batch_size=16,
        learning_rate=0.05,
        frequency_of_the_test=1,
    )
    base.update(over)
    return make_args(**base)


class TestDeviceSynth:
    def test_stand_in_goes_through_device_path(self, caplog):
        import logging

        with caplog.at_level(logging.WARNING):
            ds = load(_args())
        assert "features generated on-device" in caplog.text
        # contract fields all present and consistent
        C, nb, bs = ds.packed_train.mask.shape
        assert C == 4 and bs == 16
        assert int(ds.packed_num_samples.sum()) == ds.train_data_num == 400
        assert ds.train_data_global.x.shape[0] == C * nb

    def test_deterministic_across_loads(self):
        a, b = load(_args()), load(_args())
        np.testing.assert_array_equal(np.asarray(a.packed_train.y), np.asarray(b.packed_train.y))
        np.testing.assert_array_equal(np.asarray(a.packed_train.x), np.asarray(b.packed_train.x))

    def test_global_view_is_flattened_packed(self):
        ds = load(_args())
        C, nb, bs = ds.packed_train.mask.shape
        np.testing.assert_array_equal(
            np.asarray(ds.train_data_global.x),
            np.asarray(ds.packed_train.x).reshape((C * nb, bs) + ds.packed_train.x.shape[3:]),
        )
        # mask excludes pads: real-sample count survives the flatten
        assert float(np.asarray(ds.train_data_global.mask).sum()) == 400.0

    def test_bf16_dtype_packs_bf16(self):
        import jax.numpy as jnp

        ds = load(_args(dtype="bfloat16"))
        assert ds.packed_train.x.dtype == jnp.bfloat16
        assert ds.packed_train.y.dtype == jnp.int32

    def test_real_leaf_copy_still_wins(self, tmp_path, caplog):
        # with a LEAF dir on disk the device path must NOT trigger
        import logging

        args = _args(dataset="mnist", client_num_in_total=2, client_num_per_round=2)
        args.data_cache_dir = "tests/data"
        with caplog.at_level(logging.WARNING):
            ds = load(args)
        assert "stand-in" not in caplog.text
        assert ds.train_data_num > 0

    def test_truncation_keeps_metadata_consistent(self, caplog):
        """A skewed partition whose tail exceeds the waste cap: the
        packer warns (no silent caps) and every count in the dataset
        object reflects the packed reality — train_data_num, the
        per-client dict, packed_num_samples, and the global view's
        mask all agree."""
        import logging

        args = _args(
            synthetic_train_size=2000,
            client_num_in_total=8,
            partition_alpha=0.1,  # heavy skew
            # nb clamps to the median client's batches, so any client
            # above the median is guaranteed to lose its tail
            packing_waste_cap=1.0,
        )
        with caplog.at_level(logging.WARNING):
            ds = load(args)
        packed_total = int(np.asarray(ds.packed_num_samples).sum())
        assert ds.train_data_num == packed_total
        assert sum(ds.train_data_local_num_dict.values()) == packed_total
        assert float(np.asarray(ds.train_data_global.mask).sum()) == packed_total
        assert packed_total < 2000  # the cap bit (median clamp)
        assert "long-tail truncation" in caplog.text

    def test_homo_partition_supported(self):
        ds = load(_args(partition_method="homo"))
        sizes = list(ds.train_data_local_num_dict.values())
        assert max(sizes) - min(sizes) <= 1

    @pytest.mark.slow  # 3 full training rounds, ~30s on a 1-core box
    def test_learnable_cnn_loss_drops(self):
        from fedml_tpu.simulation import FedAvgAPI

        args = _args(comm_round=3, learning_rate=0.1)
        args = fedml_tpu.init(args)
        ds = load(args)
        model = models.create(args, ds.class_num)
        api = FedAvgAPI(args, None, ds, model)
        stats = api.train()
        assert np.isfinite(stats["train_loss"])
        assert stats["train_loss"] < np.log(62) + 0.2  # moved off init


class TestEnsureFloat:
    @pytest.mark.slow  # full ResNet-18 init + forward, ~19s on 1 core
    def test_resnet_preserves_bf16(self):
        import jax
        import jax.numpy as jnp

        from fedml_tpu.models.resnet import resnet18_gn

        m = resnet18_gn(10)
        p = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)))
        pb = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16)
            if jnp.issubdtype(a.dtype, jnp.floating)
            else a,
            p,
        )
        out = m.apply(pb, jnp.zeros((2, 32, 32, 3), jnp.bfloat16))
        assert out.dtype == jnp.bfloat16

    def test_int_input_promoted_to_f32(self):
        import jax.numpy as jnp

        from fedml_tpu.models.spec import ensure_float

        assert ensure_float(jnp.zeros((2,), jnp.uint8)).dtype == jnp.float32
        assert ensure_float(jnp.zeros((2,), jnp.bfloat16)).dtype == jnp.bfloat16
        assert ensure_float(jnp.zeros((2,), jnp.float32)).dtype == jnp.float32
