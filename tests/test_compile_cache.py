"""Persistent compilation cache (core/compile_cache.py) behind the
validated ``compile_cache_dir`` knob — ROADMAP item 5's AOT-cache
rider: warm-start the executable census from disk, count hits/misses
in telemetry."""

import os

import jax
import pytest

import fedml_tpu
from fedml_tpu import models
from fedml_tpu.arguments import Arguments
from fedml_tpu.core import compile_cache
from fedml_tpu.core.telemetry import Telemetry
from fedml_tpu.data import load
from fedml_tpu.simulation import FedAvgAPI


pytestmark = pytest.mark.usefixtures("compile_cache_reset")


def _args(**kw):
    a = Arguments()
    base = dict(
        dataset="mnist",
        synthetic_train_size=120,
        synthetic_test_size=40,
        model="lr",
        client_num_in_total=4,
        client_num_per_round=4,
        comm_round=2,
        epochs=1,
        batch_size=8,
        learning_rate=0.05,
        frequency_of_the_test=1,
    )
    base.update(kw)
    for k, v in base.items():
        setattr(a, k, v)
    a._validate()
    return a


class TestKnob:
    def test_validated(self):
        with pytest.raises(ValueError, match="compile_cache_dir"):
            _args(compile_cache_dir=3)
        a = _args(compile_cache_dir=None)  # null disables, validates
        assert a.compile_cache_dir is None

    def test_disabled_by_default(self):
        assert not compile_cache.maybe_enable_compile_cache(_args())
        assert compile_cache.enabled_dir() is None


class TestEnable:
    def test_train_populates_cache_and_telemetry(self, tmp_path):
        """A training run with the knob set writes the round/eval
        executables into the cache directory and exposes the
        miss/entry telemetry series."""
        d = str(tmp_path / "xla_cache")
        args = fedml_tpu.init(_args(compile_cache_dir=d))
        assert compile_cache.maybe_enable_compile_cache(args)
        assert compile_cache.enabled_dir() == os.path.abspath(d)
        dataset = load(args)
        model = models.create(args, dataset.class_num)
        api = FedAvgAPI(args, None, dataset, model)
        api.train()
        n = compile_cache.cache_entries()
        assert n > 0, "no executables were persisted"
        tel = Telemetry.get_instance()
        # the listener counts every compile that went through the
        # cache; a cold directory shows only misses
        assert tel.get_counter("compile_cache_misses_total") > 0

    def test_warm_restart_hits(self, tmp_path):
        """Clearing the in-process jit caches and re-running the same
        world compiles nothing new: the persistent cache serves every
        executable (hits counted, zero new entries) — the
        'warm-starts in seconds' contract, in miniature."""
        d = str(tmp_path / "xla_cache")
        # a previous test's in-process jit cache would let executables
        # skip the cold ledger (compiled-but-never-persisted), making
        # the warm replay look like it missed — start truly cold
        jax.clear_caches()
        args = fedml_tpu.init(_args(compile_cache_dir=d))
        # enable BEFORE the loader's synthesis jits so the cold ledger
        # covers every executable the warm replay will need (engine
        # inits enable it too, but by then load() has compiled)
        compile_cache.maybe_enable_compile_cache(args)
        dataset = load(args)
        model = models.create(args, dataset.class_num)
        api = FedAvgAPI(args, None, dataset, model)
        api.train()
        n_cold = compile_cache.cache_entries()
        assert n_cold > 0
        jax.clear_caches()
        Telemetry.reset()
        args2 = fedml_tpu.init(_args(compile_cache_dir=d))
        api2 = FedAvgAPI(args2, None, dataset, model)
        api2.train()
        assert compile_cache.cache_entries() == n_cold, (
            "warm replay wrote new cache entries — a cache miss on an "
            "identical executable"
        )
        tel = Telemetry.get_instance()
        # every warm compile is served from disk: hits counted, and
        # the zero-new-entries assertion above is the ground truth
        assert tel.get_counter("compile_cache_hits_total") > 0

    def test_second_directory_warns_and_keeps_first(self, tmp_path, caplog):
        a1 = _args(compile_cache_dir=str(tmp_path / "one"))
        a2 = _args(compile_cache_dir=str(tmp_path / "two"))
        assert compile_cache.maybe_enable_compile_cache(a1)
        import logging

        with caplog.at_level(logging.WARNING):
            assert compile_cache.maybe_enable_compile_cache(a2)
        assert compile_cache.enabled_dir() == os.path.abspath(
            str(tmp_path / "one")
        )
        assert any("already rooted" in r.message for r in caplog.records)
