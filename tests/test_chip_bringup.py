"""Bring-up contracts (PR 21), checked on the CPU: what must hold for a
chip run to be a chip run. The chip itself is reached only through
``python chip_smoke.py`` (see README); these tests pin the refusals and
placements around it — no CPU run or interpreted kernel can pass for a
device run, and the compile cache goes where it is put from outside.
"""

import logging
import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import pytest

from fedml_tpu import constants
from fedml_tpu.core import compile_cache
from fedml_tpu.ops.flash_attention import flash_attention, max_seq_len

pytestmark = pytest.mark.smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))



class TestChipSmokeRefusesCpu:
    def test_exits_nonzero_and_names_the_platform(self):
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py")],
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=120, cwd=REPO,
        )
        assert r.returncode != 0
        assert "platform 'cpu'" in r.stderr
        # no result line: nothing on stdout parses as the ok object
        for line in r.stdout.splitlines():
            assert not line.startswith("{"), line


class TestChipSmokeConfigs:
    """The YAMLs chip_smoke.py feeds through --cf must load and say what
    the stages claim — a typo found here costs no chip minutes."""

    def _load(self, path):
        import argparse

        from fedml_tpu.arguments import Arguments

        return Arguments(argparse.Namespace(yaml_config_file=path))

    def test_stage_a_is_the_baseline_cohort_at_full_width(self):
        import chip_smoke

        a = self._load(chip_smoke.STAGE_A_CF)
        assert (a.federated_optimizer, a.model, a.dataset) == (
            "FedAvg", "resnet18", "cifar10",
        )
        assert (a.client_num_in_total, a.client_num_per_round) == (100, 10)
        assert (a.batch_size, a.comm_round, a.dtype) == (64, 3, "bfloat16")
        assert a.synthetic_train_size == 100 * 500
        assert a.frequency_of_the_test == 1  # a loss for every round
        assert getattr(a, "mesh_shape", None) is None

    def test_stage_b_reaches_the_flash_kernel_on_one_device(self):
        import chip_smoke

        a = self._load(chip_smoke.STAGE_B_CF)
        assert (a.model, a.attention_impl, a.dtype) == (
            "transformer", "flash", "bfloat16",
        )
        assert (a.num_layers, a.num_heads, a.embed_dim, a.seq_len) == (
            12, 12, 768, 1024,
        )
        assert dict(a.mesh_shape) == {"dp": 1}
        assert a.seq_len % 128 == 0

    def test_stage_c_configs_are_stage_a_plus_a_mesh(self, tmp_path):
        import yaml

        import chip_smoke

        path = chip_smoke._mesh_cf(
            chip_smoke.STAGE_A_CF, {"data": 2, "fsdp": 2}, str(tmp_path)
        )
        with open(path) as f, open(chip_smoke.STAGE_A_CF) as g:
            mesh_cfg, base_cfg = yaml.safe_load(f), yaml.safe_load(g)
        assert mesh_cfg["train_args"].pop("mesh_shape") == {"data": 2, "fsdp": 2}
        assert mesh_cfg == base_cfg
        assert self._load(path).mesh_shape == {"data": 2, "fsdp": 2}


def _qkv(B, T, H, D, dtype=jnp.bfloat16):
    s = jax.ShapeDtypeStruct((B, T, H, D), dtype)
    return s, s, s


def _tpu_hlo(fn, *args) -> str:
    return jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()


class TestFlashCrossLowersForTpu:
    """The kernel goes through the Pallas TPU lowering from this CPU
    host (block specs, tiling and the kernel body are checked there);
    Mosaic itself sees it on the chip, in chip_smoke.py stage B."""

    @pytest.mark.parametrize(
        "shape",
        [
            (4, 4096, 8, 64),   # chip_smoke stage B, the kernel alone
            (2, 2048, 4, 128),  # D = one full lane tile
            (8, 1024, 12, 64),  # chip_smoke stage B: B*H = 96
        ],
    )
    def test_forward(self, shape):
        hlo = _tpu_hlo(lambda q, k, v: flash_attention(q, k, v, True), *_qkv(*shape))
        assert "tpu_custom_call" in hlo

    def test_backward_at_the_kernel_alone_shape(self):
        def loss(q, k, v):
            return flash_attention(q, k, v, True).astype(jnp.float32).sum()

        hlo = _tpu_hlo(jax.grad(loss, argnums=(0, 1, 2)), *_qkv(4, 4096, 8, 64))
        assert "tpu_custom_call" in hlo

    @pytest.mark.parametrize("window", [1024, None])
    def test_backward_at_the_lm_cells_shape(self, window):
        """Mellum2's layer in the benchmark's cell (a lane: B 4, T 4,096,
        32 query heads on 4 KV heads of 128): the gradient lowers to the
        forward kernel once -- as with the scan backward before PR 29 --
        and the two backward kernels, whose names hold no forward
        kernel's (the trace's roofline readers find kernels by
        substring)."""
        q = jax.ShapeDtypeStruct((4, 4096, 32, 128), jnp.bfloat16)
        kv = jax.ShapeDtypeStruct((4, 4096, 4, 128), jnp.bfloat16)

        def loss(q, k, v):
            return flash_attention(q, k, v, True, None, 512, 512, window).astype(jnp.float32).sum()

        hlo = _tpu_hlo(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
        kind = "flash_attention" if window is None else "flash_attention_window"
        other = "flash_attention_window" if window is None else "flash_attention"
        assert hlo.count("tpu_custom_call") == 3
        assert hlo.count(f'"{kind}_fwd"') == 1
        assert hlo.count(f'"{kind}_bwd_dkv"') == 1 and hlo.count(f'"{kind}_bwd_dq"') == 1
        assert hlo.count(f"{kind}_fwd") == 1 and f"{other}_fwd" not in hlo

    def test_lowers_under_the_default_matmul_precision(self):
        # fedml_tpu.init() sets matmul_precision="highest" by default;
        # the bf16 kernel must not inherit an fp32 contract precision
        with jax.default_matmul_precision("highest"):
            hlo = _tpu_hlo(
                lambda q, k, v: flash_attention(q, k, v, True), *_qkv(1, 256, 2, 64)
            )
        assert "tpu_custom_call" in hlo

    def test_cpu_lowering_interprets_and_other_platforms_raise(self):
        args = _qkv(1, 256, 2, 64)
        f = lambda q, k, v: flash_attention(q, k, v, True)
        cpu = jax.jit(f).trace(*args).lower(lowering_platforms=("cpu",)).as_text()
        assert "tpu_custom_call" not in cpu
        with pytest.raises(NotImplementedError, match="cuda"):
            jax.jit(f).trace(*args).lower(lowering_platforms=("cuda",))

    def test_untileable_shapes_raise_the_clear_error(self):
        q = jnp.zeros((1, 1000, 2, 64), jnp.bfloat16)
        with pytest.raises(ValueError, match="TPU tiling cannot take"):
            flash_attention(q, q, q, True)
        q = jnp.zeros((1, 256, 2, 64), jnp.bfloat16)
        with pytest.raises(ValueError, match="multiple of 128"):
            flash_attention(q, q, q, True, None, 64, 64)

    def test_largest_sequence_is_stated_and_enforced(self):
        t_max = max_seq_len(64, jnp.bfloat16)
        assert t_max % 128 == 0
        # f32 rows are twice as wide; D=256 takes two lane tiles
        assert max_seq_len(64, jnp.float32) == t_max // 2
        assert max_seq_len(256, jnp.bfloat16) == t_max // 2
        ok = jax.ShapeDtypeStruct((1, t_max, 1, 64), jnp.bfloat16)
        assert "tpu_custom_call" in _tpu_hlo(
            lambda q, k, v: flash_attention(q, k, v, True), ok, ok, ok
        )
        big = jax.ShapeDtypeStruct((1, t_max + 128, 1, 64), jnp.bfloat16)
        with pytest.raises(ValueError, match=f"exceeds {t_max}"):
            jax.eval_shape(lambda q, k, v: flash_attention(q, k, v, True), big, big, big)


class _Args:
    def __init__(self, compile_cache_dir=None):
        self.compile_cache_dir = compile_cache_dir


class TestCompileCachePlacement:
    def test_environment_wins_and_no_code_sets_the_dir(
        self, monkeypatch, tmp_path, caplog, compile_cache_reset
    ):
        env_dir = str(tmp_path / "from_env")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        updates = []
        real_update = jax.config.update

        def recording_update(name, value):
            updates.append(name)
            return real_update(name, value)

        monkeypatch.setattr(jax.config, "update", recording_update)
        with caplog.at_level(logging.WARNING):
            assert compile_cache.maybe_enable_compile_cache(
                _Args(str(tmp_path / "from_knob"))
            )
        assert compile_cache.enabled_dir() == env_dir
        assert compile_cache.stats()["dir"] == env_dir
        assert "jax_compilation_cache_dir" not in updates
        assert any("ignored" in r.message for r in caplog.records)

    def test_unset_on_tpu_is_the_fixed_checkout_path(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        d = compile_cache.resolve_dir(_Args())
        assert d == os.path.join(REPO, ".jax_compile_cache")
        assert d == compile_cache.CHECKOUT_CACHE_DIR
        # a fixed path: nothing of this process or of a temp dir in it
        assert str(os.getpid()) not in d
        assert not d.startswith(tempfile.gettempdir())

    def test_unset_on_cpu_stays_off(self, monkeypatch):
        # the tier-1 run must not fill the checkout
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert jax.default_backend() == "cpu"
        assert compile_cache.resolve_dir(_Args()) is None
        assert not compile_cache.maybe_enable_compile_cache(_Args())
        assert not os.path.exists(os.path.join(REPO, ".jax_compile_cache"))

    def test_knob_honoured_when_environment_is_unset(
        self, monkeypatch, tmp_path, compile_cache_reset
    ):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        knob = str(tmp_path / "from_knob")
        assert compile_cache.resolve_dir(_Args(knob)) == knob
        assert compile_cache.maybe_enable_compile_cache(_Args(knob))
        assert jax.config.jax_compilation_cache_dir == knob

    def test_init_enables_it_before_any_data_is_loaded(
        self, monkeypatch, tmp_path, compile_cache_reset
    ):
        import fedml_tpu
        from fedml_tpu.arguments import Arguments

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        args = Arguments()
        args.compile_cache_dir = str(tmp_path / "from_init")
        args._validate()
        fedml_tpu.init(args)
        assert compile_cache.enabled_dir() == str(tmp_path / "from_init")

    def test_enabling_after_a_compile_still_caches(
        self, monkeypatch, tmp_path, compile_cache_reset
    ):
        """jax 0.9 builds its cache lazily, so no reach into jax._src is
        needed when the directory arrives after the first compile."""
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        jax.jit(lambda x: x * 2.0 + 1.0)(jnp.ones(3)).block_until_ready()
        knob = str(tmp_path / "late")
        assert compile_cache.maybe_enable_compile_cache(_Args(knob))
        jax.jit(lambda x: x * 3.0 - 7.0)(jnp.ones(5)).block_until_ready()
        assert compile_cache.cache_entries() > 0
        assert compile_cache.stats()["misses"] > 0


class TestPeakTable:
    def test_unknown_device_kind_raises(self):
        with pytest.raises(ValueError, match="TPU v9"):
            constants.peak_bf16_flops("TPU v9")
        with pytest.raises(ValueError, match="TPU v9"):
            constants.hbm_bandwidth_bytes("TPU v9")
        with pytest.raises(ValueError):
            constants.peak_bf16_flops("cpu")

    def test_the_chip_at_hand(self):
        # what jax.devices()[0].device_kind reads on the v5e (PR 21)
        assert constants.peak_bf16_flops("TPU v5 lite") == 197e12
        assert constants.hbm_bandwidth_bytes("TPU v5 lite") == 819e9
        assert constants.peak_bf16_flops("TPU v5 lite0") == 197e12


class TestNoSilentMfu:
    def test_roofline_join_raises_on_an_unknown_accelerator(self):
        from fedml_tpu.analysis import perf

        measured = {("x", ""): {"count": 1.0, "sum": 1.0, "min": 1.0, "max": 1.0}}
        with pytest.raises(ValueError, match="TPU v9"):
            perf.join_roofline({"executables": []}, measured, "TPU v9")
        # the CPU is asked for by platform and simply has no peak
        assert perf.join_roofline(
            {"executables": []}, measured, "cpu"
        )["peak_bf16_flops"] is None


class TestSiloLauncherOnAChipHost:
    def test_refuses_processes_that_would_share_the_chips(self, monkeypatch):
        from fedml_tpu.cross_silo.hierarchical.launcher import launch_silo_processes

        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        with pytest.raises(RuntimeError, match="belongs to one"):
            launch_silo_processes("entry.py", 2, 1234, 5678)
