"""Round-indexed LR schedules for FL (VERDICT r3 #5).

``lr_schedule: cosine`` + ``lr_total_rounds`` decays the client LR
across the FEDERATION (constant within one local fit), unlike
``lr_total_steps`` which counts optimizer steps inside one optimizer
lifetime (the distributed trainer). The ambiguous combinations refuse
loudly (core/optimizers.py resolve_round_lr_schedule).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.conftest import make_args

pytestmark = pytest.mark.smoke


def _fl_args(**kw):
    base = dict(
        dataset="mnist",
        synthetic_train_size=80,
        synthetic_test_size=40,
        model="lr",
        client_num_in_total=4,
        client_num_per_round=4,
        partition_method="homo",
        comm_round=4,
        epochs=1,
        batch_size=10,
        learning_rate=0.5,
        frequency_of_the_test=1,
        shuffle=False,
    )
    base.update(kw)
    return make_args(**base)


def _api(args):
    from fedml_tpu import models
    from fedml_tpu.data import load
    from fedml_tpu.simulation import FedAvgAPI

    dataset = load(args)
    model = models.create(args, dataset.class_num)
    return FedAvgAPI(args, None, dataset, model), dataset


class TestResolve:
    def test_constant_is_none(self):
        from fedml_tpu.core.optimizers import resolve_round_lr_schedule

        assert resolve_round_lr_schedule(_fl_args()) is None

    def test_cosine_needs_rounds_not_steps(self):
        from fedml_tpu.core.optimizers import resolve_round_lr_schedule

        with pytest.raises(ValueError, match="lr_total_rounds"):
            resolve_round_lr_schedule(
                _fl_args(lr_schedule="cosine", lr_total_steps=100)
            )

    def test_both_bases_refused(self):
        from fedml_tpu.core.optimizers import resolve_round_lr_schedule

        with pytest.raises(ValueError, match="ambiguous"):
            resolve_round_lr_schedule(
                _fl_args(
                    lr_schedule="cosine", lr_total_steps=100, lr_total_rounds=10
                )
            )

    def test_step_path_refuses_round_base(self):
        from fedml_tpu.core.optimizers import resolve_learning_rate

        with pytest.raises(ValueError, match="round-indexed"):
            resolve_learning_rate(
                _fl_args(lr_schedule="cosine", lr_total_rounds=10)
            )

    def test_cosine_sequence(self):
        from fedml_tpu.core.optimizers import resolve_round_lr_schedule

        sched = resolve_round_lr_schedule(
            _fl_args(lr_schedule="cosine", lr_total_rounds=10)
        )
        lrs = [float(sched(r)) for r in range(10)]
        assert lrs[0] == pytest.approx(0.5)
        assert all(a > b for a, b in zip(lrs, lrs[1:]))  # strictly decays
        assert lrs[-1] < 0.02

    def test_warmup_rounds(self):
        from fedml_tpu.core.optimizers import resolve_round_lr_schedule

        sched = resolve_round_lr_schedule(
            _fl_args(lr_schedule="cosine", lr_total_rounds=10, warmup_rounds=2)
        )
        lrs = [float(sched(r)) for r in range(10)]
        # ramp starts at peak/(warm+1), NOT 0 — an LR-0 round would
        # waste a whole round of client compute
        assert lrs[0] == pytest.approx(0.5 / 3)
        assert lrs[2] == pytest.approx(0.5)  # peak after the ramp
        assert lrs[2] > lrs[5] > lrs[9]
        assert all(lr > 0 for lr in lrs)


class TestEngine:
    def test_per_round_lr_multiplier_sequence(self):
        api, _ = _api(
            _fl_args(lr_schedule="cosine", lr_total_rounds=4)
        )
        mults = [float(api._lr_mult(r)) for r in range(4)]
        import optax

        expected = optax.cosine_decay_schedule(0.5, decay_steps=4)
        for r, m in enumerate(mults):
            assert m == pytest.approx(float(expected(r)) / 0.5, rel=1e-6)

    def test_scheduled_round_equals_constant_at_that_lr(self):
        """One round at schedule(r) == one round with constant lr set to
        schedule(r): the multiplier seam is exactly an LR change."""
        args_s = _fl_args(lr_schedule="cosine", lr_total_rounds=8, comm_round=1)
        api_s, dataset = _api(args_s)

        r_probe = 3
        lr_r = 0.5 * float(api_s._lr_mult(r_probe))
        args_c = _fl_args(comm_round=1, learning_rate=lr_r)
        api_c, dataset_c = _api(args_c)
        # identical init: same seed/model
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            api_s.global_params,
            api_c.global_params,
        )

        # what train() hands the round executable: the API's sample store
        ns = jnp.asarray(dataset.packed_num_samples)
        idx = jnp.arange(4, dtype=jnp.int32)
        rng = jax.random.PRNGKey(42)
        p_s, _, _ = api_s._round_fn(
            api_s.global_params, api_s.server_state, api_s._sample_store(),
            ns, idx, rng, api_s._lr_mult(r_probe),
        )
        p_c, _, _ = api_c._round_fn(
            api_c.global_params, api_c.server_state, api_c._sample_store(),
            ns, idx, rng,
        )
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
            ),
            p_s,
            p_c,
        )

    def test_training_trajectory_decays(self):
        """End-to-end: the full train() loop applies the decaying LR —
        round-over-round global-param movement shrinks by round 8 of a
        cosine that ends at ~0."""
        args = _fl_args(
            lr_schedule="cosine", lr_total_rounds=8, comm_round=8,
            frequency_of_the_test=100,
        )
        api, _ = _api(args)
        deltas = []
        prev = jax.tree.map(np.asarray, api.global_params)

        orig = api._round_fn

        def spy(*a, **k):
            out = orig(*a, **k)
            nonlocal prev
            cur = jax.tree.map(np.asarray, out[0])
            deltas.append(
                float(
                    sum(
                        np.abs(c - p).sum()
                        for c, p in zip(
                            jax.tree.leaves(cur), jax.tree.leaves(prev)
                        )
                    )
                )
            )
            prev = cur
            return out

        api._round_fn = spy
        api.train()
        assert len(deltas) == 8
        # late rounds move far less than early ones (lr -> ~0)
        assert deltas[-1] < 0.25 * deltas[0]

    def test_custom_trainer_refused(self):
        from fedml_tpu import models
        from fedml_tpu.core.frame import ClientTrainer
        from fedml_tpu.data import load
        from fedml_tpu.simulation import FedAvgAPI

        args = _fl_args(lr_schedule="cosine", lr_total_rounds=4)
        dataset = load(args)
        model = models.create(args, dataset.class_num)

        class T(ClientTrainer):
            def make_train_fn(self, args):
                raise AssertionError("never built")

        with pytest.raises(ValueError, match="custom client_trainer"):
            FedAvgAPI(args, None, dataset, model, client_trainer=T(model, args))

    def test_decentralized_refused(self):
        from fedml_tpu import models
        from fedml_tpu.data import load
        from fedml_tpu.simulation.decentralized import DecentralizedDSGDAPI

        args = _fl_args(lr_schedule="cosine", lr_total_rounds=4)
        dataset = load(args)
        model = models.create(args, dataset.class_num)
        with pytest.raises(ValueError, match="decentralized gossip"):
            DecentralizedDSGDAPI(args, None, dataset, model)
