"""Synthetic dataset generators.

Two roles:

1. Parity with the reference's synthetic federated datasets
   (``python/fedml/data/synthetic_1_1``, ``data/fedprox`` — the FedProx
   synthetic(alpha, beta) generator): per-client logistic models drawn
   from a hierarchical Gaussian, the standard non-IID stress test.
2. Zero-egress stand-ins for download-only datasets (the reference
   auto-downloads MNIST et al. from S3, ``data/MNIST/data_loader.py:17-29``;
   this environment has no egress). Shapes/classes match the real
   datasets so models and benchmarks are identical; a real copy placed
   in ``args.data_cache_dir`` takes precedence (see loader.py).
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np


def synthetic_fedprox(
    num_clients: int = 30,
    alpha: float = 1.0,
    beta: float = 1.0,
    input_dim: int = 60,
    num_classes: int = 10,
    seed: int = 0,
    min_samples: int = 20,
    max_samples: int = 400,
) -> Tuple[List[np.ndarray], List[np.ndarray]]:
    """FedProx synthetic(alpha, beta): W_k ~ N(u_k, 1), u_k ~ N(0, alpha);
    x_k ~ N(v_k, Sigma), v_k ~ N(B_k, 1), B_k ~ N(0, beta); lognormal
    client sizes. Returns per-client (x, y) lists."""
    rng = np.random.RandomState(seed)
    sizes = np.clip(
        rng.lognormal(4, 2, num_clients).astype(int), min_samples, max_samples
    )
    diag = np.array([(j + 1) ** -1.2 for j in range(input_dim)])
    xs, ys = [], []
    for k in range(num_clients):
        u_k = rng.normal(0, alpha)
        b_k = rng.normal(0, beta)
        v_k = rng.normal(b_k, 1, input_dim)
        W = rng.normal(u_k, 1, (input_dim, num_classes))
        b = rng.normal(u_k, 1, num_classes)
        x = rng.multivariate_normal(v_k, np.diag(diag), sizes[k]).astype(np.float32)
        logits = x @ W + b
        y = np.argmax(logits, axis=1).astype(np.int64)
        xs.append(x)
        ys.append(y)
    return xs, ys


def _class_means(num_classes: int, dim: int, means_seed: int) -> np.ndarray:
    """The one class-means construction both the host and device
    stand-in generators use — train/test and host/device synthesis
    share a distribution only because this expression is shared."""
    return np.random.RandomState(means_seed).normal(
        0, 1, (num_classes, dim)
    ).astype(np.float32)


def synthetic_classification(
    n_samples: int,
    num_classes: int,
    feature_shape: Tuple[int, ...],
    seed: int = 0,
    sigma: float = 1.0,
    means_seed: int = 1234,
) -> Tuple[np.ndarray, np.ndarray]:
    """Class-conditional Gaussian blobs with learnable structure: each
    class has a mean vector; examples are mean + noise. Linear models
    reach high accuracy, so optimization dynamics are observable.

    ``means_seed`` fixes the class means independently of the sampling
    seed so train/test splits share one distribution."""
    rng = np.random.RandomState(seed)
    dim = int(np.prod(feature_shape))
    means = _class_means(num_classes, dim, means_seed)
    y = rng.randint(0, num_classes, n_samples).astype(np.int64)
    x = means[y] + sigma * rng.normal(0, 1, (n_samples, dim)).astype(np.float32)
    return x.reshape((n_samples,) + feature_shape), y


def synthetic_classification_device(
    y_packed: np.ndarray,
    feature_shape: Tuple[int, ...],
    num_classes: int,
    seed: int = 0,
    sigma: float = 1.0,
    means_seed: int = 1234,
    dtype=None,
):
    """Device-side twin of :func:`synthetic_classification`: given
    host-packed labels ``y_packed`` (any leading shape), synthesize the
    feature tensor ``means[y] + sigma * noise`` directly on the default
    device with ``jax.random``.

    Rationale: the machine with the chip has no dataset and no network,
    and features need not cross the host link at all — materializing a
    stand-in host-side would push the whole image tensor (>1 GB for a
    CIFAR-shaped 100-client federation) through host->device for
    nothing. Shipping the labels (KBs) and generating features in HBM
    makes cohort size a compute knob instead of a bandwidth one. Same distribution family
    and the same ``means_seed`` convention as the host generator (class
    means shared across train/test); the noise stream is jax's threefry
    rather than numpy's MT, which is deterministic across processes and
    backends for a given seed."""
    import jax.numpy as jnp

    dim = int(np.prod(feature_shape))
    means = _class_means(num_classes, dim, means_seed)
    return _gen_device(
        jnp.asarray(y_packed, jnp.int32),
        jnp.asarray(means),
        jnp.uint32(seed),  # uint32: RandomState's full [0, 2**32) seed domain
        jnp.float32(sigma),
        tuple(feature_shape),
        dtype or jnp.float32,
    )


def _module_jit(fn=None, **kw):
    """jax.jit at module scope, imported lazily (this module must stay
    importable without jax for the host-side numpy generators)."""
    import functools

    import jax

    return jax.jit(fn, **kw) if fn is not None else functools.partial(
        jax.jit, **kw
    )


def _gen_device_impl(y, means, seed, sigma, feature_shape, out_dtype):
    import jax
    import jax.numpy as jnp

    dim = means.shape[1]
    noise = jax.random.normal(
        jax.random.PRNGKey(seed), y.shape + (dim,), jnp.float32
    )
    x = means[y] + sigma * noise
    return x.reshape(y.shape + tuple(feature_shape)).astype(out_dtype)


def _gen_per_client_impl(y, means, client_seeds, sigma, feature_shape,
                         out_dtype):
    import jax
    import jax.numpy as jnp

    dim = means.shape[1]
    C = y.shape[0]
    flat = y.reshape(C, -1)  # [C, S] sample-ordered per client
    S = flat.shape[1]
    sample_idx = jnp.arange(S, dtype=jnp.uint32)

    def one_client(seed, ys):
        # noise[s] is a pure function of (client seed, sample index):
        # independent of which cohort slot, vmap group, or nb bucket
        # the client lands in this round — the registry's determinism
        # contract for features
        key = jax.random.PRNGKey(seed)
        keys = jax.vmap(lambda s: jax.random.fold_in(key, s))(sample_idx)
        noise = jax.vmap(
            lambda k: jax.random.normal(k, (dim,), jnp.float32)
        )(keys)
        return means[ys] + sigma * noise

    x = jax.vmap(one_client)(client_seeds, flat)
    return x.reshape(y.shape + tuple(feature_shape)).astype(out_dtype)


# jitted lazily on first use, then cached at module scope so repeat
# calls (once per cohort group per round on the registry path) hit the
# jit cache instead of rebuilding a fresh wrapper every call
_GEN_CACHE: dict = {}


def _gen_device(y, means, seed, sigma, feature_shape, out_dtype):
    fn = _GEN_CACHE.get("device")
    if fn is None:
        fn = _GEN_CACHE["device"] = _module_jit(
            static_argnames=("feature_shape", "out_dtype")
        )(_gen_device_impl)
    return fn(y, means, seed, sigma, feature_shape, out_dtype)


def synthetic_classification_device_per_client(
    y_packed: np.ndarray,
    feature_shape: Tuple[int, ...],
    num_classes: int,
    client_seeds: np.ndarray,
    sigma: float = 1.0,
    means_seed: int = 1234,
    dtype=None,
):
    """Per-client twin of :func:`synthetic_classification_device` for
    the registry path (``fedml_tpu/scale/registry.py``): ``y_packed``
    is ``[C, ...]`` with one leading row per client and
    ``client_seeds[c]`` seeds row ``c``'s noise **per sample index**,
    so a client's features are a function of the client alone — stable
    across rounds, cohort slots, and nb buckets (sample ``s`` keeps its
    noise when the client's packed shape changes). Same class-means
    convention as the host generator."""
    import jax.numpy as jnp

    dim = int(np.prod(feature_shape))
    means = _class_means(num_classes, dim, means_seed)
    fn = _GEN_CACHE.get("per_client")
    if fn is None:
        fn = _GEN_CACHE["per_client"] = _module_jit(
            static_argnames=("feature_shape", "out_dtype")
        )(_gen_per_client_impl)
    return fn(
        jnp.asarray(y_packed, jnp.int32),
        jnp.asarray(means),
        jnp.asarray(client_seeds, jnp.uint32),
        jnp.float32(sigma),
        tuple(feature_shape),
        dtype or jnp.float32,
    )


def synthetic_segmentation(
    n_samples: int,
    num_classes: int,
    feature_shape: Tuple[int, ...],
    seed: int = 0,
) -> Tuple[np.ndarray, np.ndarray]:
    """Blob-mask segmentation stand-in (pascal_voc/cityscapes shapes;
    fets2021's 4-channel MRI-modality shape uses the same generator):
    each image gets 1-3 axis-aligned rectangles of distinct foreground
    classes on a background (class 0); pixel labels follow the
    rectangles and pixel intensities encode the class, so a small
    encoder-decoder can learn the mapping."""
    h, w = feature_shape[0], feature_shape[1]
    ch = feature_shape[2] if len(feature_shape) > 2 else 3
    rng = np.random.RandomState(seed)
    palette = np.random.RandomState(4321).uniform(-1, 1, (num_classes, ch)).astype(
        np.float32
    )
    x = np.zeros((n_samples, h, w, ch), np.float32)
    y = np.zeros((n_samples, h, w), np.int64)
    for i in range(n_samples):
        x[i] = palette[0] + 0.3 * rng.normal(0, 1, (h, w, ch))
        for _ in range(rng.randint(1, 4)):
            c = rng.randint(1, num_classes)
            hh, ww = rng.randint(h // 6, h // 2), rng.randint(w // 6, w // 2)
            r0, c0 = rng.randint(0, h - hh), rng.randint(0, w - ww)
            x[i, r0 : r0 + hh, c0 : c0 + ww] = palette[c] + 0.3 * rng.normal(
                0, 1, (hh, ww, ch)
            )
            y[i, r0 : r0 + hh, c0 : c0 + ww] = c
    return x, y


# above this vocabulary the chain's rows are sparse: a dense
# [vocab, vocab] transition matrix is 800 MB at 10,004 and 1.2 GB at 12,288
_DENSE_CHAIN_VOCAB = 2048
_CHAIN_FANOUT = 16


def synthetic_sequences(
    n_samples: int,
    seq_len: int,
    vocab_size: int,
    seed: int = 0,
) -> Tuple[np.ndarray, np.ndarray]:
    """Markov-chain token streams for NWP models: x = tokens[:-1],
    y = tokens[1:]. The chain's structure makes next-token prediction
    learnable above chance. Up to ``_DENSE_CHAIN_VOCAB`` tokens a row of
    the chain is a Dirichlet(0.05) draw over the whole vocabulary; above
    it (a real vocabulary: 10,004, 12,288, 98,304) each token has
    ``_CHAIN_FANOUT`` successors drawn at random with Dirichlet(0.5)
    weights, so memory and time grow with ``n_samples * seq_len`` and
    not with ``vocab_size ** 2``."""
    rng = np.random.RandomState(seed)
    toks = np.zeros((n_samples, seq_len + 1), np.int64)
    toks[:, 0] = rng.randint(0, vocab_size, n_samples)
    if vocab_size <= _DENSE_CHAIN_VOCAB:
        # sparse row-stochastic transition matrix
        trans = rng.dirichlet(np.full(vocab_size, 0.05), size=vocab_size)
        for t in range(seq_len):
            p = trans[toks[:, t]]
            cum = p.cumsum(axis=1)
            u = rng.rand(n_samples, 1)
            toks[:, t + 1] = (u > cum).sum(axis=1)
        return toks[:, :-1], toks[:, 1:]
    succ = rng.randint(0, vocab_size, (vocab_size, _CHAIN_FANOUT))
    cum = rng.dirichlet(np.full(_CHAIN_FANOUT, 0.5), size=vocab_size).cumsum(axis=1)
    u = rng.rand(n_samples, seq_len)
    for t in range(seq_len):
        cur = toks[:, t]
        pick = np.minimum((u[:, t, None] > cum[cur]).sum(axis=1), _CHAIN_FANOUT - 1)
        toks[:, t + 1] = succ[cur, pick]
    return toks[:, :-1], toks[:, 1:]


def synthetic_multilabel(
    n_samples: int,
    num_tags: int,
    feature_shape: Tuple[int, ...],
    seed: int = 0,
    tags_per_sample: int = 3,
    sigma: float = 0.5,
    means_seed: int = 1234,
) -> Tuple[np.ndarray, np.ndarray]:
    """Multi-hot tag-prediction stand-in (stackoverflow_lr shape): each
    sample carries 1..tags_per_sample tags; features are the sum of the
    active tags' embedding vectors + noise, so a linear sigmoid model
    is learnable. Returns (x [N, *shape], y multi-hot [N, num_tags])."""
    rng = np.random.RandomState(seed)
    dim = int(np.prod(feature_shape))
    emb = np.random.RandomState(means_seed).normal(
        0, 1, (num_tags, dim)
    ).astype(np.float32)
    y = np.zeros((n_samples, num_tags), np.float32)
    x = sigma * rng.normal(0, 1, (n_samples, dim)).astype(np.float32)
    counts = rng.randint(1, tags_per_sample + 1, n_samples)
    for i in range(n_samples):
        tags = rng.choice(num_tags, counts[i], replace=False)
        y[i, tags] = 1.0
        x[i] += emb[tags].sum(axis=0)
    return x.reshape((n_samples,) + feature_shape), y
