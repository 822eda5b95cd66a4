"""Dataset dispatcher.

``load(args)`` mirrors ``fedml.data.load`` (``python/fedml/data/
data_loader.py:29`` -> ``load_synthetic_data`` ``:42-320``) and returns a
:class:`FederatedDataset` whose ``to_list()`` is the reference's
canonical 8-tuple ``[train_data_num, test_data_num, train_data_global,
test_data_global, train_data_local_num_dict, train_data_local_dict,
test_data_local_dict, class_num]`` (data_loader.py:310-320) — plus the
device-side packed federation (``packed_train`` / ``packed_test``,
leaves ``[C, nb, bs, ...]``) that the TPU simulators consume.

Dataset resolution order under ``<data_cache_dir>/<dataset>/``:

1. **naturally federated on-disk sources** — LEAF json split dirs
   (``train/*.json``; reference ``data/MNIST/data_loader.py:30-99``)
   and TFF h5 (``fed_cifar100_train.h5`` etc.; reference
   ``data/fed_cifar100/data_loader.py``) — the per-user grouping IS the
   partition, LDA is bypassed;
2. **global on-disk sources** — CIFAR python batches
   (``cifar-10-batches-py/``; reference ``cifar10/data_loader.py``) and
   the generic ``{train,test}.npz`` drop-in — LDA/homo partition
   applies;
3. synthetic stand-in with the real dataset's shapes/classes (this
   environment has no egress; the reference downloads from S3,
   ``data/MNIST/data_loader.py:17-29``), with a loud warning.
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import constants
from ..core.partition import (
    homo_partition,
    non_iid_partition_with_dirichlet_distribution,
    record_data_stats,
)
from ..core.types import Batches
from .packing import bucket_num_batches, pack_clients, pack_one
from .synthetic import (
    synthetic_classification,
    synthetic_fedprox,
    synthetic_multilabel,
    synthetic_segmentation,
    synthetic_sequences,
)

_DATASET_META = {
    # name: (feature_shape, class_num, train_n, test_n, task)
    "mnist": ((28, 28, 1), 10, 60000, 10000, "classification"),
    "femnist": ((28, 28, 1), 62, 40000, 8000, "classification"),
    "fashion_mnist": ((28, 28, 1), 10, 60000, 10000, "classification"),
    "cifar10": ((32, 32, 3), 10, 50000, 10000, "classification"),
    "cifar100": ((32, 32, 3), 100, 50000, 10000, "classification"),
    "fed_cifar100": ((32, 32, 3), 100, 50000, 10000, "classification"),
    "cinic10": ((32, 32, 3), 10, 90000, 90000, "classification"),
    "shakespeare": ((80,), 90, 16000, 2000, "nwp"),
    "fed_shakespeare": ((80,), 90, 16000, 2000, "nwp"),
    "stackoverflow_nwp": ((20,), 10004, 40000, 8000, "nwp"),
    # a token corpus with no fixed geometry (an LM fine-tune's packed
    # sequences): args.seq_len and args.vocab_size state the sequence
    # length and the vocabulary; stand-in only (a Markov chain over the
    # stated vocabulary, data/synthetic.py), no real copy is looked for
    "token_stream": ((1024,), 32000, 2048, 256, "nwp"),
    # multi-label tag prediction (reference data/stackoverflow_lr/:
    # 10k bag-of-words -> 500 tags); the synthetic stand-in shrinks the
    # feature dim so the offline path stays in memory
    "stackoverflow_lr": ((10000,), 500, 40000, 8000, "tag_prediction"),
    # image-folder / CSV-federated image benchmarks (ImageNet-style
    # class dirs; Landmarks user->image csv). Stand-in shapes keep H/W
    # modest — real copies under data_cache_dir override, resized to
    # args.image_size (default 64).
    "imagenet": ((64, 64, 3), 1000, 20000, 2000, "classification"),
    "gld23k": ((64, 64, 3), 203, 23080, 1000, "classification"),
    "gld160k": ((64, 64, 3), 2028, 164172, 1000, "classification"),
    # federated segmentation (fedseg benchmarks; stand-in shapes keep
    # H/W modest — a real copy under data_cache_dir overrides)
    "pascal_voc": ((64, 64, 3), 21, 4000, 800, "segmentation"),
    "coco_seg": ((64, 64, 3), 81, 4000, 800, "segmentation"),
    "cityscapes": ((64, 64, 3), 19, 3000, 500, "segmentation"),
    # FeTS2021 (reference data/FeTS2021/download.sh — the BraTS2018
    # multimodal brain-MRI federation, partitioned by institution):
    # 4 modality channels (T1/T1Gd/T2/FLAIR slices), 4 label classes
    # (background + 3 tumor sub-regions). Stand-in keeps H/W modest; a
    # real extracted copy under data_cache_dir/fets2021 (train/test
    # npz or image folders) overrides.
    "fets2021": ((64, 64, 4), 4, 2000, 400, "segmentation"),
}


@dataclasses.dataclass
class FederatedDataset:
    train_data_num: int
    test_data_num: int
    train_data_global: Batches
    test_data_global: Batches
    train_data_local_num_dict: Dict[int, int]
    train_data_local_dict: Dict[int, Batches]
    test_data_local_dict: Dict[int, Optional[Batches]]
    class_num: int
    # TPU-side stacked federation (client axis leading)
    packed_train: Batches = None
    packed_num_samples: np.ndarray = None
    packed_test: Optional[Batches] = None
    client_num: int = 0
    task: str = "classification"
    # vertically-partitioned source (party CSVs): ([feats_k [N,d_k]...],
    # labels [N]). The VFL scenario uses the real per-party columns as
    # the vertical split; horizontal consumers see the concatenation.
    vfl_parties: Optional[Tuple[List[np.ndarray], np.ndarray]] = None

    def to_list(self) -> List:
        """Reference 8-tuple (data_loader.py:310-320)."""
        return [
            self.train_data_num,
            self.test_data_num,
            self.train_data_global,
            self.test_data_global,
            self.train_data_local_num_dict,
            self.train_data_local_dict,
            self.test_data_local_dict,
            self.class_num,
        ]


def _try_load_real(name: str, cache_dir: str, args=None, probe: bool = False):
    """Global real data: CIFAR python batches, ImageNet-style image
    folders, else the generic {train,test}.npz drop-in.

    ``probe=True`` answers "is real data on disk?" (returns bool) using
    the SAME branches as loading — one resolution order, so a source
    added here is automatically seen by the device-synthesis gate
    (loader._device_synth_classification) and can never be shadowed by
    a stand-in."""
    d = os.path.join(cache_dir or "", name)
    if name in ("cifar10", "cifar100"):
        from .ingest import cifar_batches_available, load_cifar_batches

        if cifar_batches_available(d, name):
            return True if probe else load_cifar_batches(d, name)
    from .ingest import image_folder_available, load_image_folder

    if image_folder_available(d):
        if probe:
            return True
        hw = int(getattr(args, "image_size", 64) or 64) if args else 64
        # 5-tuple: the folder structure is authoritative for class
        # count (truncated ImageNet copies carry fewer classes)
        return load_image_folder(d, (hw, hw))
    tr, te = os.path.join(d, "train.npz"), os.path.join(d, "test.npz")
    if os.path.exists(tr) and os.path.exists(te):
        if probe:
            return True
        a, b = np.load(tr), np.load(te)
        return (a["x"], a["y"], b["x"], b["y"])
    return False if probe else None


def _try_load_federated(name: str, cache_dir: str, args=None):
    """Naturally-federated on-disk sources: LEAF json dirs, TFF h5.
    Returns per-client (xs_tr, ys_tr, xs_te, ys_te) or None."""
    if name not in _DATASET_META:
        return None
    d = os.path.join(cache_dir or "", name)
    shape, _class_num, _, _, task = _DATASET_META[name]
    from . import ingest
    from .leaf import leaf_available, load_leaf

    if cache_dir and bool(getattr(args, "download", False)):
        from .download import dataset_downloadable, download_dataset

        # a LEAF json dir only counts as a local copy for tasks that
        # actually consume it — the nwp path deliberately ignores LEAF
        # json (see below), so it must not suppress the h5 download
        has_local = ingest.tff_h5_available(d, name) or (
            task != "nwp" and leaf_available(d)
        )
        if dataset_downloadable(name) and not has_local:
            # reference parity: auto-fetch the dataset's archives
            # (data/<ds>/download*.sh; MNIST data_loader.py:17-29) —
            # with offline grace
            download_dataset(name, cache_dir)

    out = None
    if leaf_available(d):
        if task == "nwp":
            # LEAF shakespeare stores raw strings with single-char
            # targets — a different task shape than the per-token TFF
            # pipeline; use the TFF h5 artifact for nwp datasets
            logging.warning(
                "dataset %s: LEAF json found but nwp ingestion uses the "
                "TFF h5 artifact; ignoring the json dir", name,
            )
        else:
            out = load_leaf(d, feature_shape=shape)
    if out is None and ingest.tff_h5_available(d, name):
        out = ingest.load_tff_h5(d, name)
    if out is None and ingest.landmarks_csv_available(d):
        hw = int(getattr(args, "image_size", 64) or 64)
        out = ingest.load_landmarks_csv(d, (hw, hw))
    if out is None:
        return None
    xs_tr, ys_tr, xs_te, ys_te = out
    if task == "classification" and xs_tr and xs_tr[0].ndim == len(shape):
        # h5 images stored [N,H,W] (fed_emnist 'pixels') -> add channel
        xs_tr = [x.reshape(x.shape + (1,)) for x in xs_tr]
        xs_te = [x.reshape(x.shape + (1,)) for x in xs_te]
    return xs_tr, ys_tr, xs_te, ys_te



def _standin_shape_and_sizes(args, name: str):
    """Shared stand-in geometry for the host (:func:`_raw_data`) and
    device (:func:`_device_synth_classification`) synthesis paths: the
    dataset's feature shape (resized-image datasets follow
    ``args.image_size`` exactly like the real ingestion) and the
    synthetic train/test sizes with their default caps. One
    implementation, so the two paths can never drift apart for the same
    args."""
    shape, class_num, train_n, test_n, task = _DATASET_META[name]
    if name in ("imagenet", "gld23k", "gld160k"):
        hw = int(getattr(args, "image_size", 64) or 64)
        shape = (hw, hw, 3)
    if task == "nwp" and getattr(args, "seq_len", None):
        # args.seq_len drives the stand-in sequence length (real copies
        # keep their own; the model's max_len already follows args) —
        # without this the long-context path would silently train at
        # the dataset's canonical length (shakespeare: 80)
        shape = (int(args.seq_len),)
    if name == "token_stream" and int(getattr(args, "vocab_size", 0) or 0) > 0:
        class_num = int(args.vocab_size)
    train_n = int(getattr(args, "synthetic_train_size", min(train_n, 20000)))
    test_n = int(getattr(args, "synthetic_test_size", min(test_n, 4000)))
    return shape, class_num, train_n, test_n, task


def _device_synth_classification(
    args, name: str, client_num: int, batch_size: int, seed: int
):
    """Zero-transfer stand-in path: when a classification dataset has no
    local copy (the machine with the chip has no dataset and no
    network), partition host-side labels and synthesize the feature
    tensor directly on the device — features need not cross the host
    link, which carries only labels + masks (KBs, vs >1 GB of images
    for a CIFAR-shaped 100-client federation). Returns a full :class:`FederatedDataset`, or
    None when the path does not apply (real data on disk, non-image
    task, non-stand-in dataset). Distribution family and the shared
    class-means convention match ``synthetic_classification``."""
    if name not in _DATASET_META:
        return None
    shape, class_num, train_n, test_n, task = _standin_shape_and_sizes(args, name)
    if task != "classification":
        return None
    if _try_load_real(name, getattr(args, "data_cache_dir", None), args, probe=True):
        return None
    logging.warning(
        "dataset %s: no local copy under data_cache_dir; using synthetic "
        "stand-in with identical shapes/classes (features generated "
        "on-device)", name,
    )
    import jax.numpy as jnp

    from .packing import pack_labels_np
    from .synthetic import synthetic_classification_device

    rng = np.random.RandomState(seed)
    y_tr = rng.randint(0, class_num, train_n).astype(np.int64)
    y_te = np.random.RandomState(seed + 1).randint(0, class_num, test_n).astype(
        np.int64
    )

    method = getattr(args, "partition_method", constants.PARTITION_HETERO)
    if method == constants.PARTITION_HOMO:
        idx_map = homo_partition(train_n, client_num, seed)
    else:
        idx_map = non_iid_partition_with_dirichlet_distribution(
            y_tr, client_num, class_num,
            float(getattr(args, "partition_alpha", 0.5)), seed=seed,
        )
        record_data_stats(y_tr, idx_map)
    ys_tr = [y_tr[idx_map[i]] for i in range(client_num)]
    te_map = homo_partition(test_n, client_num, seed + 1)
    ys_te = [y_te[te_map[i]] for i in range(client_num)]

    waste_cap = float(getattr(args, "packing_waste_cap", 4.0) or 4.0)
    x_dtype = (
        jnp.bfloat16
        if str(getattr(args, "dtype", "float32") or "float32") == "bfloat16"
        else jnp.float32
    )
    sigma = float(getattr(args, "synthetic_sigma", 1.0) or 1.0)

    def build(ys, gen_seed):
        nb = bucket_num_batches([len(y) for y in ys], batch_size, waste_cap=waste_cap)
        y_p, mask, num_samples = pack_labels_np(ys, batch_size, num_batches=nb)
        x = synthetic_classification_device(
            y_p, shape, class_num, seed=gen_seed, sigma=sigma, dtype=x_dtype
        )
        packed = Batches(
            x=x, y=jnp.asarray(y_p, jnp.int32), mask=jnp.asarray(mask)
        )
        return packed, num_samples

    packed_train, num_samples = build(ys_tr, seed)
    packed_test, test_num_samples = build(ys_te, seed + 1)

    def flat(p: Batches) -> Batches:
        # the global view is the packed federation flattened on-device:
        # exactly the packed samples (long-tail clients past the
        # waste-cap are truncated by the packer, which warns), mask
        # keeps ragged semantics exact (pads carry mask 0). No second
        # transfer, no host concat.
        C, nb = p.mask.shape[0], p.mask.shape[1]
        return Batches(
            x=p.x.reshape((C * nb,) + p.x.shape[2:]),
            y=p.y.reshape((C * nb,) + p.y.shape[2:]),
            mask=p.mask.reshape(C * nb, -1),
        )

    # counts reflect the packed federation (post-truncation), so every
    # view of this dataset object agrees with its metadata
    sizes = [int(n) for n in num_samples]
    return FederatedDataset(
        train_data_num=int(sum(sizes)),
        test_data_num=int(test_num_samples.sum()),
        train_data_global=flat(packed_train),
        test_data_global=flat(packed_test),
        train_data_local_num_dict={i: int(s) for i, s in enumerate(sizes)},
        train_data_local_dict={
            i: _client_view(packed_train, i) for i in range(client_num)
        },
        test_data_local_dict={
            i: _client_view(packed_test, i) for i in range(client_num)
        },
        class_num=class_num,
        packed_train=packed_train,
        packed_num_samples=np.asarray(num_samples),
        packed_test=packed_test,
        client_num=client_num,
        task=task,
    )


def _resolve_poisoned_idxs(args, client_num: int, seed: int):
    """Which client indexes are attackers: an explicit
    ``poisoned_client_idxs`` list wins; else ``poisoned_client_fraction``
    of the federation, drawn with a seed-derived RandomState (the
    fedavg_robust convention — attacker identity is part of the
    experiment config, never of the run's training randomness)."""
    idxs = getattr(args, "poisoned_client_idxs", None)
    if idxs:
        # USER ORDER preserved: a poison_type LIST pairs with these
        # 1:1 positionally, so sorting/deduping here would silently
        # swap attacks between clients
        out = [int(i) for i in idxs]
        if len(set(out)) != len(out):
            raise ValueError(
                f"poisoned_client_idxs {out} contains duplicates"
            )
        bad = [i for i in out if not 0 <= i < client_num]
        if bad:
            raise ValueError(
                f"poisoned_client_idxs {bad} out of range for "
                f"{client_num} clients"
            )
        return out
    frac = float(getattr(args, "poisoned_client_fraction", 0.0) or 0.0)
    if frac <= 0:
        return []
    k = min(client_num, max(1, int(round(frac * client_num))))
    return sorted(
        np.random.RandomState(seed + 77)
        .choice(client_num, k, replace=False)
        .tolist()
    )


def _maybe_poison_clients(args, xs_tr, ys_tr, class_num: int, seed: int, task: str):
    """Poisoned-world wiring (``args.poison_type`` — the reference
    fork's fedavg_robust experiment shape): apply ``data/poison.py``
    attacks to the configured attacker clients' TRAIN shards before
    packing. ``poison_type`` is one type for every attacker or a list
    paired with ``poisoned_client_idxs`` (mixed-attack worlds, e.g.
    label_flip + backdoor_pattern). Loud by design: a poisoned world
    always logs who is poisoned with what."""
    ptype = getattr(args, "poison_type", None) or None
    if ptype is None:
        return xs_tr, ys_tr
    if task != "classification":
        raise ValueError(
            f"poison_type={ptype!r} supports classification datasets "
            f"only (got task={task!r})"
        )
    target = int(getattr(args, "target_label", 0) or 0)
    if not 0 <= target < class_num:
        # an out-of-head target would one_hot to an all-zero row and
        # train the attackers on garbage SILENTLY — a different
        # experiment than the config claims
        raise ValueError(
            f"target_label={target} out of range for {class_num} classes"
        )
    from .poison import poison_clients

    if isinstance(ptype, (list, tuple)) and not getattr(
        args, "poisoned_client_idxs", None
    ):
        # a list pairs 1:1 positionally; zipping it against a
        # fraction-drawn (seed-dependent, sorted) attacker set would
        # assign attacks to arbitrary clients silently
        raise ValueError(
            "poison_type as a list pairs 1:1 with poisoned_client_idxs; "
            "set the idxs explicitly (poisoned_client_fraction draws an "
            "arbitrary attacker set)"
        )
    client_num = len(xs_tr)
    idxs = _resolve_poisoned_idxs(args, client_num, seed)
    if not idxs:
        raise ValueError(
            "poison_type is set but no attacker clients are configured; "
            "set poisoned_client_idxs or poisoned_client_fraction"
        )
    xs_tr, ys_tr, _ = poison_clients(
        xs_tr, ys_tr, ptype, class_num, idxs,
        target_label=target,
        fraction=float(getattr(args, "poison_sample_fraction", 1.0) or 1.0),
        data_cache_dir=getattr(args, "data_cache_dir", None),
    )
    logging.warning(
        "POISONED WORLD: clients %s carry %s (target_label=%s)",
        idxs, ptype, target,
    )
    return xs_tr, ys_tr


def _widen_class_num(name: str, class_num: int, observed: int) -> int:
    """Custom/truncated on-disk copies may carry ids beyond the
    canonical class count; widen the head rather than training silently
    degenerate one-hots."""
    if observed > class_num:
        logging.warning(
            "dataset %s: observed class id %d >= canonical class count "
            "%d; widening to %d", name, observed - 1, class_num, observed,
        )
        return observed
    return class_num


def _raw_data(args) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, int, str]:
    name = getattr(args, "dataset", "synthetic").lower()
    seed = int(getattr(args, "random_seed", 0))
    if name.startswith("synthetic"):
        # FedProx synthetic(alpha,beta): natively federated — handled by caller
        raise RuntimeError("synthetic handled separately")
    if name not in _DATASET_META:
        raise ValueError(f"unknown dataset {name!r}")
    shape, class_num, train_n, test_n, task = _standin_shape_and_sizes(args, name)
    real = _try_load_real(name, getattr(args, "data_cache_dir", None), args)
    if real is not None:
        if len(real) == 5:  # loader knows its own class count
            x_tr, y_tr, x_te, y_te, class_num = real
        else:
            x_tr, y_tr, x_te, y_te = real
        return x_tr, y_tr, x_te, y_te, class_num, task
    logging.warning(
        "dataset %s: no local copy under data_cache_dir; using synthetic "
        "stand-in with identical shapes/classes",
        name,
    )
    if task == "nwp":
        seq_len, vocab = shape[0], class_num
        x_tr, y_tr = synthetic_sequences(train_n, seq_len, vocab, seed)
        x_te, y_te = synthetic_sequences(test_n, seq_len, vocab, seed + 1)
    elif task == "tag_prediction":
        dim = int(getattr(args, "synthetic_feature_dim", 2000))
        x_tr, y_tr = synthetic_multilabel(train_n, class_num, (dim,), seed)
        x_te, y_te = synthetic_multilabel(test_n, class_num, (dim,), seed + 1)
    elif task == "segmentation":
        x_tr, y_tr = synthetic_segmentation(train_n, class_num, shape, seed)
        x_te, y_te = synthetic_segmentation(test_n, class_num, shape, seed + 1)
    else:
        x_tr, y_tr = synthetic_classification(train_n, class_num, shape, seed)
        x_te, y_te = synthetic_classification(test_n, class_num, shape, seed + 1)
    return x_tr, y_tr, x_te, y_te, class_num, task


def _registry_dataset(args) -> FederatedDataset:
    """Slim dataset for the planet-scale registry path
    (``fedml_tpu/scale/``): the population is NOT materialized here —
    no per-client arrays, no packed federation, no local dicts
    proportional to ``client_registry_size``. Cohort data is generated
    on demand by the registry each round; this object carries only the
    task geometry (class count, feature shape via the eval packs) and
    fixed-size global eval holdouts."""
    name = getattr(args, "dataset", "synthetic").lower()
    seed = int(getattr(args, "random_seed", 0))
    registry_size = int(args.client_registry_size)
    if getattr(args, "poison_type", None):
        raise ValueError(
            "poison_type is not supported with client_registry_size: "
            "registry cohorts synthesize data on demand and the "
            "attacks mutate eagerly-materialized shards"
        )
    if name.startswith("synthetic"):
        shape = (int(getattr(args, "input_dim", 60)),)
        class_num = int(getattr(args, "output_dim", 10))
    else:
        if name not in _DATASET_META:
            raise ValueError(f"unknown dataset {name!r}")
        shape, class_num, _, _, task = _standin_shape_and_sizes(args, name)
        if task != "classification":
            raise ValueError(
                f"client_registry_size supports classification datasets "
                f"only (dataset {name!r} is task={task!r})"
            )
    # fixed-size eval holdouts (a registry run's eval cost must not
    # scale with the population); synthetic_*_size caps still win down
    train_n = min(int(getattr(args, "synthetic_train_size", 4096)), 4096)
    test_n = min(int(getattr(args, "synthetic_test_size", 2048)), 2048)
    sigma = float(getattr(args, "synthetic_sigma", 1.0) or 1.0)
    x_tr, y_tr = synthetic_classification(
        train_n, class_num, shape, seed=seed + 3, sigma=sigma
    )
    x_te, y_te = synthetic_classification(
        test_n, class_num, shape, seed=seed + 4, sigma=sigma
    )
    import jax.numpy as jnp

    x_dtype = (
        jnp.bfloat16
        if str(getattr(args, "dtype", "float32") or "float32") == "bfloat16"
        else jnp.float32
    )
    batch_size = int(args.batch_size)
    logging.warning(
        "dataset %s: client_registry_size=%d active — population lives "
        "as columnar registry state, per-round cohorts are materialized "
        "on demand; this dataset object carries eval holdouts only",
        name, registry_size,
    )
    return FederatedDataset(
        train_data_num=train_n,
        test_data_num=test_n,
        train_data_global=pack_one(x_tr, y_tr, batch_size, x_dtype=x_dtype),
        test_data_global=pack_one(x_te, y_te, batch_size, x_dtype=x_dtype),
        train_data_local_num_dict={},
        train_data_local_dict={},
        test_data_local_dict={},
        class_num=class_num,
        packed_train=None,
        packed_num_samples=None,
        packed_test=None,
        client_num=registry_size,
        task="classification",
    )


def load(args) -> FederatedDataset:
    """Load + partition + pack (data_loader.py:29 entry)."""
    name = getattr(args, "dataset", "synthetic").lower()
    if int(getattr(args, "client_registry_size", 0) or 0) > 0:
        # planet-scale registry (fedml_tpu/scale/): NEVER build
        # per-client lists/arrays proportional to the registered
        # population — cohorts materialize on demand each round
        return _registry_dataset(args)
    client_num = int(args.client_num_in_total)
    batch_size = int(args.batch_size)
    seed = int(getattr(args, "random_seed", 0))

    # vertically-partitioned party CSVs (NUS-WIDE / lending-club style)
    # take priority for ANY dataset name — the files define the data
    cache = getattr(args, "data_cache_dir", None)
    if cache:
        from .ingest import vfl_party_csvs_available

        vfl_dir = os.path.join(cache, name)
        if vfl_party_csvs_available(vfl_dir):
            if getattr(args, "poison_type", None):
                # loud-by-design: the data/poison.py attacks mutate
                # horizontal per-client label/feature shards, which a
                # vertical party split does not have — ignoring the
                # knob would claim a poisoned world and train clean
                raise ValueError(
                    f"poison_type={args.poison_type!r} is not supported "
                    f"for VFL party-CSV datasets (found {vfl_dir!r})"
                )
            return _load_vfl_dataset(args, vfl_dir, client_num, batch_size, seed)

    if name.startswith("synthetic"):
        xs, ys = synthetic_fedprox(
            num_clients=client_num,
            alpha=float(getattr(args, "synthetic_alpha", 1.0)),
            beta=float(getattr(args, "synthetic_beta", 1.0)),
            input_dim=int(getattr(args, "input_dim", 60)),
            num_classes=int(getattr(args, "output_dim", 10)),
            seed=seed,
        )
        class_num = int(getattr(args, "output_dim", 10))
        task = "classification"
        # 80/20 split per client
        xs_tr, ys_tr, xs_te, ys_te = [], [], [], []
        for x, y in zip(xs, ys):
            k = max(1, int(0.8 * len(x)))
            xs_tr.append(x[:k]); ys_tr.append(y[:k])
            xs_te.append(x[k:]); ys_te.append(y[k:])
    elif (
        fed := _try_load_federated(name, getattr(args, "data_cache_dir", None), args)
    ) is not None:
        # naturally federated: the on-disk per-user split IS the
        # partition (no LDA). Fold users onto the requested client
        # count; cap the config when it asks for more clients than the
        # dataset has users.
        from .ingest import regroup_clients

        _, class_num, _, _, task = _DATASET_META[name]
        xs_tr, ys_tr, xs_te, ys_te = fed
        if task == "tag_prediction" and xs_tr:
            # model factory sizes the input layer off args (real copies
            # may differ from the synthetic stand-in's bow dim)
            args.input_dim = int(xs_tr[0].shape[-1])
        n_users = len(xs_tr)
        if client_num > n_users:
            logging.warning(
                "dataset %s has %d users < client_num_in_total=%d; capping",
                name, n_users, client_num,
            )
            client_num = n_users
            args.client_num_in_total = n_users
            args.client_num_per_round = min(int(args.client_num_per_round), n_users)
        xs_tr, ys_tr = regroup_clients(xs_tr, ys_tr, client_num)
        xs_te, ys_te = regroup_clients(xs_te, ys_te, client_num)
        if task == "classification":
            observed = (
                max((int(y.max()) for y in ys_tr + ys_te if len(y)), default=-1)
                + 1
            )
            class_num = _widen_class_num(name, class_num, observed)
    else:
        # a poisoned world needs host-side feature arrays (trigger
        # stamps / edge-case injection mutate x), so the zero-transfer
        # device-synth shortcut does not apply
        dev_ds = (
            None
            if getattr(args, "poison_type", None)
            else _device_synth_classification(
                args, name, client_num, batch_size, seed
            )
        )
        if dev_ds is not None:
            return dev_ds
        x_tr, y_tr, x_te, y_te, class_num, task = _raw_data(args)
        if task == "classification":
            observed = int(max(y_tr.max(initial=-1), y_te.max(initial=-1))) + 1
            class_num = _widen_class_num(name, class_num, observed)
        if task == "tag_prediction":
            # model factory sizes the input layer off args (the bow dim
            # differs between real data and the synthetic stand-in)
            args.input_dim = int(x_tr.shape[-1])
        method = getattr(args, "partition_method", constants.PARTITION_HETERO)
        if method == constants.PARTITION_HOMO:
            idx_map = homo_partition(len(y_tr), client_num, seed)
            part_labels = None
        elif task == "tag_prediction":
            # multi-hot labels: LDA partitions on each sample's
            # dominant tag (the reference's stackoverflow split is
            # naturally federated; this applies to synthetic/npz data)
            part_labels = np.argmax(y_tr, axis=-1)
            idx_map = non_iid_partition_with_dirichlet_distribution(
                part_labels, client_num, class_num,
                float(getattr(args, "partition_alpha", 0.5)), seed=seed,
            )
        elif task == "segmentation":
            # multi-label LDA (the partitioner's fedseg branch): per
            # foreground class, the index array of images containing it;
            # void labels (>= class_num, e.g. 255) excluded
            flat = y_tr.reshape(len(y_tr), -1)
            per_class = [
                np.where([(row == k).any() for row in flat])[0]
                for k in range(class_num)
            ]
            idx_map = non_iid_partition_with_dirichlet_distribution(
                per_class, client_num, class_num,
                float(getattr(args, "partition_alpha", 0.5)),
                task="segmentation", seed=seed,
            )
            # the same image can carry several classes -> dedupe per client
            idx_map = {i: np.unique(v) for i, v in idx_map.items()}
            part_labels = None
        else:
            part_labels = y_tr
            idx_map = non_iid_partition_with_dirichlet_distribution(
                part_labels, client_num, class_num,
                float(getattr(args, "partition_alpha", 0.5)), seed=seed,
            )
        if part_labels is not None:
            record_data_stats(part_labels, idx_map)
        xs_tr = [x_tr[idx_map[i]] for i in range(client_num)]
        ys_tr = [y_tr[idx_map[i]] for i in range(client_num)]
        # test side: shard uniformly (reference gives each client a
        # local test loader over the global test set slice)
        te_map = homo_partition(len(y_te), client_num, seed + 1)
        xs_te = [x_te[te_map[i]] for i in range(client_num)]
        ys_te = [y_te[te_map[i]] for i in range(client_num)]

    # poisoning applies AFTER partitioning (attacks are per-client) and
    # BEFORE packing, so every downstream view — packed federation,
    # global eval set slices, local dicts — sees the attacker's data
    xs_tr, ys_tr = _maybe_poison_clients(args, xs_tr, ys_tr, class_num, seed, task)

    import jax.numpy as jnp

    # float features follow args.dtype, matching the device-synth path
    # (_device_synth_classification) so stand-in and real-data runs of
    # the same config see identical input precision (advisor r4)
    if task == "nwp":
        x_dtype = jnp.int32
    elif str(getattr(args, "dtype", "float32") or "float32") == "bfloat16":
        x_dtype = jnp.bfloat16
    else:
        x_dtype = jnp.float32

    waste_cap = float(getattr(args, "packing_waste_cap", 4.0) or 4.0)
    sizes = [len(x) for x in xs_tr]
    nb = bucket_num_batches(sizes, batch_size, waste_cap=waste_cap)
    packed_train, num_samples = pack_clients(
        xs_tr, ys_tr, batch_size, num_batches=nb, x_dtype=x_dtype
    )
    nb_te = bucket_num_batches([len(x) for x in xs_te], batch_size, waste_cap=waste_cap)
    packed_test, _ = pack_clients(
        xs_te, ys_te, batch_size, num_batches=nb_te, x_dtype=x_dtype
    )

    x_tr_all = np.concatenate(xs_tr)
    y_tr_all = np.concatenate(ys_tr)
    x_te_all = np.concatenate(xs_te)
    y_te_all = np.concatenate(ys_te)
    train_global = pack_one(x_tr_all, y_tr_all, batch_size, x_dtype=x_dtype)
    test_global = pack_one(x_te_all, y_te_all, batch_size, x_dtype=x_dtype)

    local_train = {i: _client_view(packed_train, i) for i in range(client_num)}
    local_test = {i: _client_view(packed_test, i) for i in range(client_num)}

    return FederatedDataset(
        train_data_num=int(sum(sizes)),
        test_data_num=int(len(y_te_all)),
        train_data_global=train_global,
        test_data_global=test_global,
        train_data_local_num_dict={i: int(s) for i, s in enumerate(sizes)},
        train_data_local_dict=local_train,
        test_data_local_dict=local_test,
        class_num=class_num,
        packed_train=packed_train,
        packed_num_samples=np.asarray(num_samples),
        packed_test=packed_test,
        client_num=client_num,
        task=task,
    )


def _client_view(stacked: Batches, i: int) -> Batches:
    return Batches(x=stacked.x[i], y=stacked.y[i], mask=stacked.mask[i])


def _load_vfl_dataset(
    args, vfl_dir: str, client_num: int, batch_size: int, seed: int
) -> FederatedDataset:
    """Party CSVs -> FederatedDataset. The per-party arrays ride on
    ``vfl_parties`` for the VFL scenario; horizontal consumers get the
    column-concatenated features (homo partition — vertical data has no
    per-client label skew by construction)."""
    from .ingest import load_vfl_party_csvs, vfl_train_test_split

    feats, labels = load_vfl_party_csvs(vfl_dir)
    class_num = int(labels.max()) + 1
    f_tr, y_tr, f_te, y_te = vfl_train_test_split(feats, labels, seed)
    x_tr = np.concatenate([f.reshape(len(f), -1) for f in f_tr], axis=1)
    x_te = np.concatenate([f.reshape(len(f), -1) for f in f_te], axis=1)
    args.input_dim = int(x_tr.shape[1])

    idx_map = homo_partition(len(y_tr), client_num, seed)
    te_map = homo_partition(len(y_te), client_num, seed + 1)
    xs_tr = [x_tr[idx_map[i]] for i in range(client_num)]
    ys_tr = [y_tr[idx_map[i]] for i in range(client_num)]
    xs_te = [x_te[te_map[i]] for i in range(client_num)]
    ys_te = [y_te[te_map[i]] for i in range(client_num)]

    import jax.numpy as jnp

    sizes = [len(x) for x in xs_tr]
    nb = bucket_num_batches(sizes, batch_size)
    packed_train, num_samples = pack_clients(xs_tr, ys_tr, batch_size, num_batches=nb)
    nb_te = bucket_num_batches([len(x) for x in xs_te], batch_size)
    packed_test, _ = pack_clients(xs_te, ys_te, batch_size, num_batches=nb_te)
    train_global = pack_one(x_tr, y_tr, batch_size)
    test_global = pack_one(x_te, y_te, batch_size)
    return FederatedDataset(
        train_data_num=int(len(y_tr)),
        test_data_num=int(len(y_te)),
        train_data_global=train_global,
        test_data_global=test_global,
        train_data_local_num_dict={i: int(s) for i, s in enumerate(sizes)},
        train_data_local_dict={
            i: _client_view(packed_train, i) for i in range(client_num)
        },
        test_data_local_dict={
            i: _client_view(packed_test, i) for i in range(client_num)
        },
        class_num=class_num,
        packed_train=packed_train,
        packed_num_samples=np.asarray(num_samples),
        packed_test=packed_test,
        client_num=client_num,
        task="classification",
        vfl_parties=(feats, labels),
    )
