"""Model zoo + factory.

``create(args, output_dim)`` mirrors ``fedml.model.create``
(``python/fedml/model/model_hub.py:13-53``): dispatch keyed on
``(args.model, args.dataset)``, returning a :class:`FedModel` handle.
"""

from __future__ import annotations

import jax.numpy as jnp

from .spec import FedModel
from .linear import LogisticRegression, MLP
from .cnn import CNNFedAvg, CNNCifar
from .resnet import resnet18_gn, resnet56
from .rnn import RNNOriginalFedAvg, RNNStackOverflow
from .mobilenet import MobileNetV1, MobileNetV3Small
from .vgg import vgg
from .efficientnet import efficientnet

__all__ = ["FedModel", "create"]

_IMAGE_SHAPES = {
    "mnist": (28, 28, 1),
    "femnist": (28, 28, 1),
    "fashion_mnist": (28, 28, 1),
    "cifar10": (32, 32, 3),
    "cifar100": (32, 32, 3),
    "cinic10": (32, 32, 3),
    "fed_cifar100": (32, 32, 3),
    # 4 MRI-modality channels (FeTS2021 / BraTS slices)
    "fets2021": (64, 64, 4),
}


def _example_shape(args, default=(28, 28, 1)):
    ds = getattr(args, "dataset", "synthetic").lower()
    if ds == "synthetic" or ds == "stackoverflow_lr":
        # flat-feature datasets: the loader records the realized dim
        # (synthetic fedprox input_dim; stackoverflow bag-of-words)
        dim = int(getattr(args, "input_dim", 60))
        return (dim,)
    if ds in ("imagenet", "gld23k", "gld160k"):
        # resized-image ingestion: H/W follow args.image_size
        hw = int(getattr(args, "image_size", 64) or 64)
        return (hw, hw, 3)
    return _IMAGE_SHAPES.get(ds, default)


def _lm_geometry(args, output_dim: int):
    """``(vocab, seq_len)`` of a transformer LM. The dataset's class_num
    is the vocabulary's floor (see the rnn branch's note on
    out-of-range embedding look-ups)."""
    vocab = max(int(getattr(args, "vocab_size", 0) or 0), output_dim)
    return vocab, int(getattr(args, "seq_len", 64))


def create(args, output_dim: int) -> FedModel:
    """Factory (model_hub.py:13-53 semantics)."""
    name = getattr(args, "model", "lr").lower()
    ds = getattr(args, "dataset", "synthetic").lower()

    # multi-label tag prediction (model_hub pairs lr/stackoverflow_lr):
    # same linear/MLP modules, sigmoid-BCE task
    task = "tag_prediction" if ds == "stackoverflow_lr" else "classification"
    if name == "lr":
        return FedModel(
            name="lr",
            module=LogisticRegression(output_dim),
            task=task,
            example_shape=_example_shape(args),
        )
    if name == "mlp":
        hidden = int(getattr(args, "hidden_dim", 64))
        return FedModel(
            name="mlp",
            module=MLP(hidden, output_dim),
            task=task,
            example_shape=_example_shape(args),
        )
    if name == "cnn":
        rgb = ("cifar10", "cifar100", "cinic10", "fed_cifar100",
               "imagenet", "gld23k", "gld160k")
        if ds in rgb:
            return FedModel(
                name="cnn_cifar",
                module=CNNCifar(output_dim),
                task="classification",
                example_shape=_example_shape(args, (32, 32, 3)),
            )
        return FedModel(
            name="cnn",
            module=CNNFedAvg(output_dim),
            task="classification",
            example_shape=(28, 28, 1),
        )
    if name in ("resnet18", "resnet18_gn"):
        return FedModel(
            name="resnet18_gn",
            module=resnet18_gn(output_dim),
            task="classification",
            example_shape=_example_shape(args, (32, 32, 3)),
        )
    if name in ("resnet56", "resnet"):
        return FedModel(
            name="resnet56",
            module=resnet56(output_dim),
            task="classification",
            example_shape=_example_shape(args, (32, 32, 3)),
        )
    if name == "mobilenet":
        return FedModel(
            name="mobilenet",
            module=MobileNetV1(output_dim),
            task="classification",
            example_shape=_example_shape(args, (32, 32, 3)),
        )
    if name in ("mobilenet_v3", "mobilenetv3"):
        return FedModel(
            name="mobilenet_v3",
            module=MobileNetV3Small(output_dim),
            task="classification",
            example_shape=_example_shape(args, (32, 32, 3)),
        )
    if name.startswith("vgg"):
        return FedModel(
            name=name,
            module=vgg(name, output_dim),
            task="classification",
            example_shape=_example_shape(args, (32, 32, 3)),
        )
    if name.startswith("efficientnet"):
        return FedModel(
            name=name,
            module=efficientnet(name, output_dim),
            task="classification",
            example_shape=_example_shape(args, (32, 32, 3)),
        )
    if name == "rnn":
        # vocab must cover the dataset's token ids: an undersized vocab
        # makes every OOB embed lookup NaN-fill (eager) or silently
        # clamp (jit) — so the dataset's class_num is the floor. An
        # explicit vocab_size still wins over the historical default.
        if "stackoverflow" in ds:
            vocab = max(int(getattr(args, "vocab_size", 0) or 10004), output_dim)
            return FedModel(
                name="rnn_stackoverflow",
                module=RNNStackOverflow(vocab_size=vocab),
                task="nwp",
                example_shape=(int(getattr(args, "seq_len", 20)),),
                example_dtype=jnp.int32,
            )
        vocab = max(int(getattr(args, "vocab_size", 0) or 90), output_dim)
        return FedModel(
            name="rnn_fedavg",
            module=RNNOriginalFedAvg(vocab_size=vocab),
            task="nwp",
            example_shape=(int(getattr(args, "seq_len", 80)),),
            example_dtype=jnp.int32,
        )
    if name == "deeplab":
        from .deeplab import DeepLabLite

        return FedModel(
            name="deeplab_lite",
            module=DeepLabLite(
                num_classes=output_dim,
                width=int(getattr(args, "seg_width", 32)),
            ),
            task="segmentation",
            example_shape=_example_shape(args, (64, 64, 3)),
        )
    if name == "darts":
        from .darts import DARTSNetwork

        return FedModel(
            name="darts_search",
            module=DARTSNetwork(
                num_classes=output_dim,
                width=int(getattr(args, "nas_width", 16)),
                num_cells=int(getattr(args, "nas_cells", 2)),
                steps=int(getattr(args, "nas_steps", 2)),
            ),
            task="classification",
            example_shape=_example_shape(args, (32, 32, 3)),
        )
    if name == "transformer":
        from .transformer import TransformerLM

        vocab, seq_len = _lm_geometry(args, output_dim)
        return FedModel(
            name="transformer_lm",
            module=TransformerLM(
                vocab_size=vocab,
                num_layers=int(getattr(args, "num_layers", 2)),
                num_heads=int(getattr(args, "num_heads", 4)),
                embed_dim=int(getattr(args, "embed_dim", 128)),
                max_len=max(seq_len, int(getattr(args, "max_len", 512))),
                attention=getattr(args, "attention_impl", "full"),
                remat=bool(getattr(args, "remat", False)),
            ),
            task="nwp",
            example_shape=(seq_len,),
            example_dtype=jnp.int32,
        )
    if name == "moe_transformer":
        from .moe import MoETransformerLM

        vocab, seq_len = _lm_geometry(args, output_dim)
        return FedModel(
            name="moe_transformer_lm",
            module=MoETransformerLM(
                vocab_size=vocab,
                num_layers=int(getattr(args, "num_layers", 2)),
                num_heads=int(getattr(args, "num_heads", 4)),
                embed_dim=int(getattr(args, "embed_dim", 128)),
                max_len=max(seq_len, int(getattr(args, "max_len", 512))),
                num_experts=int(getattr(args, "num_experts", 8)),
                capacity_factor=float(getattr(args, "capacity_factor", 1.25)),
                moe_every=int(getattr(args, "moe_every", 2)),
                attention=getattr(args, "attention_impl", "full"),
                remat=bool(getattr(args, "remat", False)),
            ),
            task="nwp",
            example_shape=(seq_len,),
            example_dtype=jnp.int32,
        )
    if name == "moe_decoder":
        from ..parallel.expert import experts_held
        from flax.core import freeze

        from .decoder import FULL, SSM, MoEDecoderLM, rope_parameters_from_args

        vocab, seq_len = _lm_geometry(args, output_dim)
        num_experts = int(getattr(args, "num_experts", 8))
        layer_types = getattr(args, "layer_types", None) or (
            [FULL] * int(getattr(args, "num_layers", 2)))
        ssm = None
        if SSM in layer_types:  # the state-space mixer's sizes (Mamba2Mixer's fields)
            ssm = freeze(dict(
                num_heads=int(getattr(args, "ssm_num_heads", 4)),
                head_dim=int(getattr(args, "ssm_head_dim", 64)),
                groups=int(getattr(args, "ssm_groups", 1)),
                state_size=int(getattr(args, "ssm_state_size", 128)),
                conv_taps=int(getattr(args, "ssm_conv_kernel", 4)),
                chunk_size=int(getattr(args, "ssm_chunk_size", 128)),
            ))
        return FedModel(
            name="moe_decoder_lm",
            module=MoEDecoderLM(
                vocab_size=vocab,
                hidden_size=int(getattr(args, "hidden_size", 256)),
                layer_types=tuple(layer_types),
                num_heads=int(getattr(args, "num_heads", 4)),
                num_kv_heads=int(getattr(args, "num_kv_heads", 2)),
                head_dim=int(getattr(args, "head_dim", 64)),
                sliding_window=int(getattr(args, "sliding_window", 1024)),
                rope_parameters=rope_parameters_from_args(args),
                num_experts=num_experts,
                experts_per_token=int(getattr(args, "experts_per_token", 2)),
                expert_dim=int(getattr(args, "expert_dim", 128)),
                experts_held=tuple(experts_held(
                    num_experts,
                    int(getattr(args, "expert_parallel", 1) or 1),
                    int(getattr(args, "expert_rank", 0) or 0),
                )),
                norm_topk_prob=bool(getattr(args, "norm_topk_prob", True)),
                rms_norm_eps=float(getattr(args, "rms_norm_eps", 1e-6)),
                attention=getattr(args, "attention_impl", "full"),
                remat=bool(getattr(args, "remat", False)),
                num_dense_layers=int(getattr(args, "num_dense_layers", 0) or 0),
                intermediate_size=int(getattr(args, "intermediate_size", 0) or 0),
                conv_L_cache=int(getattr(args, "conv_L_cache", 3)),
                router_scoring=getattr(args, "router_scoring", "softmax"),
                use_expert_bias=bool(getattr(args, "use_expert_bias", False)),
                norm_topk_eps=float(getattr(args, "norm_topk_eps", 0.0)),
                tie_word_embeddings=bool(getattr(args, "tie_word_embeddings", False)),
                sublayers=bool(getattr(args, "sublayers", False)),
                qk_norm=bool(getattr(args, "qk_norm", True)),
                expert_activation=getattr(args, "expert_activation", "gated_silu"),
                shared_expert_dim=int(getattr(args, "shared_expert_dim", 0) or 0),
                routed_scaling_factor=float(getattr(args, "routed_scaling_factor", 1.0)),
                expert_chunk_loads=float(getattr(args, "expert_chunk_loads", 1.25)),
                ssm=ssm,
                output_gate=bool(getattr(args, "output_gate", False)),
                post_norms=bool(getattr(args, "post_norms", False)),
                embedding_multiplier=float(getattr(args, "embedding_multiplier", 1.0)),
            ),
            task="nwp",
            example_shape=(seq_len,),
            example_dtype=jnp.int32,
        )
    raise ValueError(f"model {name!r} (dataset {ds!r}) not in the model hub")
