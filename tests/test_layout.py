"""PartitionSpec layout table (parallel/layout.py) — the (data, fsdp)
mesh's canonical placement contract.

Satellite coverage (ISSUE 15): every parameter class in the frame
models resolves to a spec whose axes exist on the mesh,
replicated-vs-sharded leaves round-trip through NamedSharding
byte-exactly, and an unknown parameter class fails loudly.
"""

import jax
import numpy as np
import pytest

import fedml_tpu
from fedml_tpu import models
from fedml_tpu.arguments import Arguments
from fedml_tpu.parallel.layout import (
    PARAM_CLASSES,
    SpecLayout,
    build_fed_mesh,
    classify_param,
    cohort_axis_size,
    fed_mesh_shape,
    is_fed_mesh,
    param_spec,
    shard_tree,
    tree_specs,
)


def _zoo_params(model_name, dataset="mnist", class_num=10):
    a = Arguments()
    a.model = model_name
    a.dataset = dataset
    a._validate()
    m = models.create(a, class_num)
    return jax.eval_shape(m.init, jax.random.PRNGKey(0))


class TestClassification:
    # the frame zoo's whole leaf vocabulary, across conv / dense /
    # recurrent / transformer families
    ZOO = (
        ("lr", "mnist"),
        ("cnn", "femnist"),
        ("resnet18_gn", "cifar10"),
        ("mobilenet", "cifar10"),
        ("vgg11", "cifar10"),
        ("rnn", "shakespeare"),
        ("transformer", "shakespeare"),
    )

    @pytest.mark.parametrize("model_name,dataset", ZOO)
    def test_every_frame_model_leaf_resolves(
        self, eight_devices, model_name, dataset
    ):
        """Every leaf of every frame model classifies into the closed
        vocabulary and its canonical spec names only axes that exist
        on the mesh."""
        mesh = build_fed_mesh(mesh_shape={"data": 4, "fsdp": 2})
        params = _zoo_params(model_name, dataset)
        specs = tree_specs(params, mesh)
        for spec, leaf in zip(jax.tree.leaves(specs), jax.tree.leaves(params)):
            assert len(spec) <= len(leaf.shape)
            for axis_name in spec:
                if axis_name is not None:
                    assert axis_name in mesh.axis_names

    def test_classification_vocabulary(self):
        assert classify_param("kernel", 2) == "dense_kernel"
        assert classify_param("kernel", 3) == "dense_kernel"
        assert classify_param("kernel", 4) == "conv_kernel"
        assert classify_param("embedding", 2) == "embedding"
        assert classify_param("bias", 1) == "vector"
        assert classify_param("scale", 1) == "vector"
        assert classify_param("count", 0) == "scalar"  # optax state

    def test_unknown_parameter_class_fails_loudly(self):
        """A new rank>=2 leaf family must be added to the table
        deliberately — silent replication would quietly forfeit the
        fsdp HBM win."""
        with pytest.raises(ValueError, match="unknown parameter class"):
            classify_param("mystery_weight", 2)
        with pytest.raises(ValueError, match="unknown parameter class"):
            SpecLayout().spec_for("nope", 2)
        with pytest.raises(ValueError, match="unknown parameter class"):
            SpecLayout().sharded_axis("nope", 2)

    def test_server_optimizer_state_classifies(self, eight_devices):
        """FedOpt's optax state mirrors param shapes plus rank-0
        counts — the whole tree resolves through the same table (the
        'optimizer state along fsdp' half of the layout contract)."""
        import optax

        mesh = build_fed_mesh(mesh_shape={"data": 4, "fsdp": 2})
        params = _zoo_params("cnn", "femnist")
        state = jax.eval_shape(optax.adam(1e-3).init, params)
        specs = tree_specs(state, mesh)  # must not raise
        assert len(jax.tree.leaves(specs)) == len(jax.tree.leaves(state))


class TestSpecTable:
    def test_canonical_axes(self):
        layout = SpecLayout()
        # dense kernels shard the leading (gather-at-use) axis
        assert layout.sharded_axis("dense_kernel", 2) == 0
        # conv kernels shard output channels (HWIO last axis)
        assert layout.sharded_axis("conv_kernel", 4) == 3
        # embeddings shard vocab rows
        assert layout.sharded_axis("embedding", 2) == 0
        # vectors/scalars replicate
        assert layout.sharded_axis("vector", 1) is None
        assert layout.sharded_axis("scalar", 0) is None

    def test_indivisible_dim_degrades_to_replication(self):
        layout = SpecLayout()
        from jax.sharding import PartitionSpec as P

        # 7 rows over fsdp=2: placement must not constrain geometry
        assert param_spec(layout, "kernel", (7, 5), 2) == P()
        assert param_spec(layout, "kernel", (8, 5), 2) == P("fsdp", None)

    def test_cohort_spec_leads_with_data(self):
        from jax.sharding import PartitionSpec as P

        assert SpecLayout().cohort(3) == P("data", None, None)


class TestPlacement:
    def test_replicated_vs_sharded_roundtrip(self, eight_devices):
        """shard_tree places kernels fsdp-sharded and vectors
        replicated; both round-trip through NamedSharding
        BYTE-EXACTLY (placement is layout, never arithmetic)."""
        mesh = build_fed_mesh(mesh_shape={"data": 4, "fsdp": 2})
        rng = np.random.RandomState(3)
        tree = {
            "Dense_0": {
                "kernel": np.asarray(rng.randn(8, 4), np.float32),
                "bias": np.asarray(rng.randn(4), np.float32),
            }
        }
        placed = shard_tree(tree, mesh)
        k, b = placed["Dense_0"]["kernel"], placed["Dense_0"]["bias"]
        assert k.sharding.spec == SpecLayout().dense_kernel(2)
        assert b.sharding.spec == SpecLayout().vector()
        # sharded-at-rest: each device holds 1/fsdp of the kernel rows
        assert {s.data.shape for s in k.addressable_shards} == {(4, 4)}
        jax.tree.map(
            lambda a, p: np.testing.assert_array_equal(a, np.asarray(p)),
            tree, placed,
        )

    def test_indivisible_leaf_places_replicated(self, eight_devices):
        mesh = build_fed_mesh(mesh_shape={"data": 4, "fsdp": 2})
        tree = {"kernel": np.ones((7, 3), np.float32)}
        placed = shard_tree(tree, mesh)
        assert placed["kernel"].sharding.spec == SpecLayout().vector()
        np.testing.assert_array_equal(np.asarray(placed["kernel"]), tree["kernel"])


class TestFedMeshConstruction:
    def test_build_and_introspect(self, eight_devices):
        mesh = build_fed_mesh(mesh_shape={"data": 4, "fsdp": 2})
        assert mesh.axis_names == ("data", "fsdp")
        assert mesh.shape == {"data": 4, "fsdp": 2}
        assert is_fed_mesh(mesh)
        assert cohort_axis_size(mesh) == 4

    def test_default_all_devices_on_data(self, eight_devices):
        mesh = build_fed_mesh()
        assert mesh.shape == {"data": 8, "fsdp": 1}

    def test_explicit_subset_mesh(self, eight_devices):
        """{'data': 1, 'fsdp': 1} — the single-chip baseline world
        every sharded shape is compared against."""
        mesh = build_fed_mesh(mesh_shape={"data": 1, "fsdp": 1})
        assert mesh.shape == {"data": 1, "fsdp": 1}
        assert is_fed_mesh(mesh)

    def test_shape_validation(self, eight_devices):
        with pytest.raises(ValueError, match="needs 16 devices"):
            build_fed_mesh(mesh_shape={"data": 8, "fsdp": 2})
        with pytest.raises(ValueError, match="unknown axes"):
            build_fed_mesh(mesh_shape={"clients": 8})
        # the null-naming rule: explicit zeros never silently auto-size
        with pytest.raises(ValueError, match="must be >= 1"):
            build_fed_mesh(mesh_shape={"data": 0, "fsdp": 2})
        with pytest.raises(ValueError, match="exceeds the 8 available"):
            build_fed_mesh(mesh_shape={"fsdp": 16})

    def test_fed_mesh_shape_dispatch(self):
        assert fed_mesh_shape({"data": 4, "fsdp": 2})
        assert fed_mesh_shape({"fsdp": 2})
        assert fed_mesh_shape({"data": 8})
        assert not fed_mesh_shape({"clients": 4, "data": 2})  # legacy
        assert not fed_mesh_shape(None)

    def test_legacy_mesh_is_not_fed(self, eight_devices):
        from fedml_tpu.parallel.mesh import build_mesh

        legacy = build_mesh(mesh_shape={"clients": 4, "data": 2})
        assert not is_fed_mesh(legacy)
        assert cohort_axis_size(legacy) == 4
        assert cohort_axis_size(None) == 1
