"""Masked loss / metric functions.

Every loss takes a validity ``mask`` because the simulator packs ragged
per-client datasets into static-shape padded batches (XLA needs static
shapes; the reference's torch loaders are ragged, see
``data/MNIST/data_loader.py:75-99``). Masked-out examples contribute zero
loss and zero gradient.

The task set mirrors the reference's per-task trainers
(``simulation/single_process/fedavg/my_model_trainer_classification.py``,
``my_model_trainer_nwp.py``, ``my_model_trainer_tag_prediction.py``).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


def _mean_over_mask(values: Array, mask: Array) -> Array:
    denom = jnp.maximum(mask.sum(), 1.0)
    return (values * mask).sum() / denom


def softmax_cross_entropy(
    logits: Array, labels: Array, mask: Array
) -> Tuple[Array, Dict[str, Array]]:
    """Classification loss (reference trainer: CrossEntropyLoss,
    my_model_trainer_classification.py:30)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    loss = _mean_over_mask(-ll, mask)
    correct = (jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32)
    acc = _mean_over_mask(correct, mask)
    return loss, {
        "loss": loss,
        "correct": (correct * mask).sum(),
        "count": mask.sum(),
        "acc": acc,
    }


def token_cross_entropy(
    logits: Array, labels: Array, mask: Array
) -> Tuple[Array, Dict[str, Array]]:
    """Next-word/char prediction: logits [*, T, V], labels [*, T].

    ``mask`` may be the per-example mask [*] (what the packed-batch
    pipeline passes) — it is broadcast over time here — or a per-token
    mask [*, T] for PAD-aware corpora; reference NWP trainer masks PAD
    the same way (my_model_trainer_nwp.py). Counts are in tokens.
    """
    if mask.ndim == labels.ndim - 1:
        mask = jnp.broadcast_to(mask[..., None], labels.shape)
    # HLO op metadata only: with the LM's output head this is the scope
    # the device trace's head_loss_device_ms reads
    with jax.named_scope("lm.head_loss"):
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        loss = _mean_over_mask(-ll, mask)
        correct = (jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32)
        acc = _mean_over_mask(correct, mask)
    return loss, {
        "loss": loss,
        "correct": (correct * mask).sum(),
        "count": mask.sum(),
        "acc": acc,
    }


def sigmoid_bce(
    logits: Array, labels: Array, mask: Array
) -> Tuple[Array, Dict[str, Array]]:
    """Multi-label tag prediction (reference: BCELoss in
    my_model_trainer_tag_prediction.py); labels are multi-hot [*, L]."""
    labels = labels.astype(jnp.float32)
    per = jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    per_example = per.mean(axis=-1)
    loss = _mean_over_mask(per_example, mask)
    pred = (logits > 0).astype(jnp.float32)
    tp = ((pred * labels).sum(axis=-1) * mask).sum()
    fp = ((pred * (1 - labels)).sum(axis=-1) * mask).sum()
    fn = (((1 - pred) * labels).sum(axis=-1) * mask).sum()
    return loss, {
        "loss": loss,
        "tp": tp,
        "fp": fp,
        "fn": fn,
        "count": mask.sum(),
        "correct": tp,  # for uniform reporting
    }


def pixel_cross_entropy(
    logits: Array, labels: Array, mask: Array, ignore_index: int = 255
) -> Tuple[Array, Dict[str, Array]]:
    """Semantic segmentation (FedSeg trainer semantics): logits
    [*, H, W, C], labels [*, H, W]; ``mask`` is the per-example
    validity [*] broadcast over pixels. Pixels labelled
    ``ignore_index`` (the canonical 255 void label) carry no loss and
    no metric weight. Counts are in valid pixels; otherwise identical
    to :func:`token_cross_entropy` with a 2-D "time" axis."""
    pm = jnp.broadcast_to(mask[..., None, None], labels.shape)
    pm = pm * (labels != ignore_index)
    safe_labels = jnp.where(labels == ignore_index, 0, labels)
    return token_cross_entropy(logits, safe_labels, pm)


LOSSES = {
    "classification": softmax_cross_entropy,
    "nwp": token_cross_entropy,
    "tag_prediction": sigmoid_bce,
    "segmentation": pixel_cross_entropy,
}
