"""Long-context subsystem: ring/Ulysses sequence parallelism + flash kernel."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedml_tpu.ops.flash_attention import flash_attention
from fedml_tpu.parallel.sequence import (
    full_attention,
    make_sequence_sharded_attention,
    ring_attention,
    ulysses_attention,
)

B, T, H, D = 2, 64, 4, 16


def _qkv(seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
    return mk(), mk(), mk()


@pytest.fixture(scope="module")
def sp_mesh():
    devs = jax.devices()
    assert len(devs) == 8
    return Mesh(np.array(devs), ("sp",))


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_full_attention(self, sp_mesh, causal):
        q, k, v = _qkv()
        want = full_attention(q, k, v, causal=causal)
        attn = make_sequence_sharded_attention(
            sp_mesh, strategy="ring", causal=causal
        )
        got = jax.jit(attn)(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    @pytest.mark.parametrize("causal", [True, False])
    def test_chunked_hops_match_full_attention(self, sp_mesh, causal):
        """sp_ring_block chunks each hop's K/V shard — same online
        softmax in more steps; must be exact vs the dense oracle AND
        vs the unchunked ring (per-chip panel [Tq, bk] not [Tq, Tk])."""
        q, k, v = _qkv()
        want = full_attention(q, k, v, causal=causal)
        bk = (T // 8) // 2  # two chunks per hop
        attn = make_sequence_sharded_attention(
            sp_mesh, strategy="ring", causal=causal, ring_block_k=bk
        )
        got = jax.jit(attn)(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    def test_chunked_rejects_indivisible_block(self, sp_mesh):
        q, k, v = _qkv()
        attn = make_sequence_sharded_attention(
            sp_mesh, strategy="ring", ring_block_k=(T // 8) - 1
        )
        with pytest.raises(ValueError, match="block_k"):
            jax.jit(attn)(q, k, v)

    def test_chunked_gradients_match(self, sp_mesh):
        q, k, v = _qkv(1)
        bk = (T // 8) // 2
        attn = make_sequence_sharded_attention(
            sp_mesh, strategy="ring", causal=True, ring_block_k=bk
        )

        def loss_ring(q, k, v):
            return (attn(q, k, v) ** 2).sum()

        def loss_full(q, k, v):
            return (full_attention(q, k, v, causal=True) ** 2).sum()

        g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
        g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ring, g_full):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)

    def test_gradients_match(self, sp_mesh):
        q, k, v = _qkv(1)
        attn = make_sequence_sharded_attention(sp_mesh, strategy="ring", causal=True)

        def loss_ring(q, k, v):
            return (attn(q, k, v) ** 2).sum()

        def loss_full(q, k, v):
            return (full_attention(q, k, v, causal=True) ** 2).sum()

        g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
        g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ring, g_full):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)

    def test_memory_shape_is_blockwise(self, sp_mesh):
        """The jaxpr under shard_map only ever holds [Tq/n, Tk/n] score
        blocks — full [T, T] never materializes per shard. Recurses into
        every sub-jaxpr (shard_map body, scan body, ...)."""

        def all_shapes(jaxpr):
            for eqn in jaxpr.eqns:
                for var in eqn.outvars:
                    if hasattr(var.aval, "shape"):
                        yield tuple(var.aval.shape)
                for p in eqn.params.values():
                    inner = getattr(p, "jaxpr", p)
                    if hasattr(inner, "eqns"):
                        yield from all_shapes(inner)

        q, k, v = _qkv(2)
        attn = make_sequence_sharded_attention(sp_mesh, strategy="ring", causal=True)
        shapes = list(all_shapes(jax.make_jaxpr(attn)(q, k, v).jaxpr))
        score_like = [s for s in shapes if len(s) >= 2 and s[-2:] == (T, T)]
        assert not score_like, score_like
        # sanity: the recursion actually saw the per-shard blocks
        n = 8
        assert any(s[-2:] == (T // n, T // n) for s in shapes if len(s) >= 2)


class TestUlysses:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_full_attention(self, sp_mesh, causal):
        # Ulysses re-shards heads over the axis: H must divide n; the
        # gathered sequence runs the flash kernel, which tiles T=128
        rng = np.random.default_rng(3)
        mk = lambda: jnp.asarray(rng.normal(size=(B, 128, 8, D)).astype(np.float32))
        q, k, v = mk(), mk(), mk()
        want = full_attention(q, k, v, causal=causal)
        attn = make_sequence_sharded_attention(
            sp_mesh, strategy="ulysses", causal=causal
        )
        got = jax.jit(attn)(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    def test_untileable_sequence_raises_not_dense(self, sp_mesh):
        """A gathered T the flash kernel cannot tile is an error on
        every platform — never a quiet switch to dense attention."""
        rng = np.random.default_rng(3)
        mk = lambda: jnp.asarray(rng.normal(size=(B, T, 8, D)).astype(np.float32))
        attn = make_sequence_sharded_attention(sp_mesh, strategy="ulysses")
        with pytest.raises(ValueError, match="TPU tiling cannot take"):
            jax.jit(attn)(mk(), mk(), mk())

    def test_rejects_indivisible_heads(self, sp_mesh):
        q, k, v = _qkv(3)  # H=4 over 8 devices
        attn = make_sequence_sharded_attention(sp_mesh, strategy="ulysses")
        with pytest.raises(ValueError, match="divisible"):
            jax.jit(attn)(q, k, v)


FT = 256  # two 128-blocks each way: the smallest multi-block flash shape


def _flash_qkv(seed):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(1, FT, 2, D)).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.smoke
class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_full(self, causal):
        q, k, v = _flash_qkv(4)
        want = full_attention(q, k, v, causal=causal)
        got = flash_attention(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    @pytest.mark.parametrize("causal", [True, False])
    def test_gradients(self, causal):
        q, k, v = _flash_qkv(5)

        def loss_flash(q, k, v):
            return (flash_attention(q, k, v, causal) ** 2).sum()

        def loss_full(q, k, v):
            return (full_attention(q, k, v, causal=causal) ** 2).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)

    def test_rejects_untileable_shapes(self):
        """One rule on every platform: 128-multiple blocks that divide
        T. What the TPU tiling cannot take raises here on CPU too."""
        q, k, v = _flash_qkv(6)
        with pytest.raises(ValueError, match="multiple of 128"):
            flash_attention(q, k, v, True, None, 48, 48)
        with pytest.raises(ValueError, match="TPU tiling cannot take"):
            flash_attention(q[:, :192], k[:, :192], v[:, :192], True)
        q64, k64, v64 = _qkv(6)  # T=64: smaller than one block
        with pytest.raises(ValueError, match="TPU tiling cannot take"):
            flash_attention(q64, k64, v64, True)

    def test_backward_is_blockwise(self):
        """The custom backward is Pallas kernels: the gradient's jaxpr
        holds ``pallas_call``s (forward, dK/dV, dQ), no ``scan``, and no
        value anywhere -- the kernels' bodies included -- with trailing
        dims (T, T). (Traced only, at a T above the kernels' largest
        tile: a 512 x 512 score tile in VMEM is the point.)"""

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                yield eqn.primitive.name, [
                    tuple(v.aval.shape) for v in eqn.outvars if hasattr(v.aval, "shape")
                ]
                for p in eqn.params.values():
                    for inner in p if isinstance(p, (list, tuple)) else [p]:
                        inner = getattr(inner, "jaxpr", inner)
                        if hasattr(inner, "eqns"):
                            yield from walk(inner)

        t = 4 * FT
        q = jax.ShapeDtypeStruct((1, t, 2, D), jnp.float32)

        def loss(q, k, v):
            return (flash_attention(q, k, v, True) ** 2).sum()

        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)
        eqns = list(walk(jaxpr.jaxpr))
        names = [name for name, _ in eqns]
        # one branch a platform (cpu interpreted, tpu compiled), each the
        # forward and the two backward kernels
        assert names.count("pallas_call") >= 3
        assert "scan" not in names
        shapes = [s for _, outs in eqns for s in outs]
        assert not any(s[-2:] == (t, t) for s in shapes if len(s) >= 2)


class TestBf16Ring:
    def test_bf16_ring_tracks_f32_oracle(self, sp_mesh):
        rng = np.random.default_rng(8)
        mk = lambda: jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
        q, k, v = mk(), mk(), mk()
        want = full_attention(q, k, v, causal=True)
        attn = make_sequence_sharded_attention(sp_mesh, strategy="ring", causal=True)
        got = jax.jit(attn)(
            q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
        )
        assert got.dtype == jnp.bfloat16
        # f32 accumulation keeps bf16 inputs within bf16 rounding of the
        # f32 oracle (pure-bf16 accumulation drifts ~10x worse)
        err = np.abs(np.asarray(got, np.float32) - np.asarray(want)).max()
        assert err < 0.05, err


class TestTransformerFL:
    @pytest.mark.slow  # re-tiered by measurement (>4s fast-gate budget)
    def test_transformer_federated_training(self, args_factory):
        from fedml_tpu import models
        from fedml_tpu.data import load
        from fedml_tpu.simulation import FedAvgAPI

        args = args_factory(
            dataset="shakespeare",
            synthetic_train_size=160,
            synthetic_test_size=40,
            model="transformer",
            vocab_size=90,
            seq_len=32,
            num_layers=1,
            num_heads=2,
            embed_dim=32,
            client_num_in_total=4,
            client_num_per_round=4,
            comm_round=2,
            epochs=1,
            batch_size=8,
            learning_rate=0.1,
            frequency_of_the_test=1,
        )
        dataset = load(args)
        model = models.create(args, dataset.class_num)
        api = FedAvgAPI(args, None, dataset, model)
        stats = api.train()
        assert np.isfinite(stats["test_loss"])
        assert api.history[-1]["train_loss"] < api.history[0]["train_loss"] * 1.2

    def test_flash_variant_same_loss(self, args_factory):
        from fedml_tpu import models

        common = dict(
            dataset="shakespeare", model="transformer", vocab_size=50,
            seq_len=128, num_layers=1, num_heads=2, embed_dim=32,
        )
        m_full = models.create(args_factory(**common, attention_impl="full"), 50)
        m_flash = models.create(args_factory(**common, attention_impl="flash"), 50)
        params = m_full.init(jax.random.PRNGKey(0))
        x = jnp.asarray(np.random.default_rng(0).integers(0, 50, (2, 128)))
        np.testing.assert_allclose(
            np.asarray(m_full.apply(params, x)),
            np.asarray(m_flash.apply(params, x)),
            atol=2e-5,
        )
