"""Host-side packing: ragged per-client numpy data -> static-shape
device arrays.

This is the load-bearing bridge between the reference's ragged
torch-DataLoader world and XLA's static shapes (SURVEY.md §7 "hard
parts": padded/bucketed client batching). Each client's samples are
padded up to ``num_batches * batch_size`` with a {0,1} mask; a
federation is stacked along a leading client axis so the whole cohort is
ONE pytree — ready for vmap or for sharding the client axis over a mesh.
"""

from __future__ import annotations

import logging
from typing import List, Optional, Sequence, Tuple

import numpy as np
import jax.numpy as jnp

from ..core.types import Batches


def _pack_one_np(
    x: np.ndarray,
    y: np.ndarray,
    batch_size: int,
    num_batches: Optional[int] = None,
    allow_truncate: bool = False,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side core of :func:`pack_one`: pad/truncate + reshape into
    ``([nb, bs, ...x], [nb, bs, ...y], mask[nb, bs])`` numpy arrays.

    Kept device-free so :func:`pack_clients` can stack a whole
    federation host-side and pay ONE host->device transfer per leaf —
    many small per-client transfers are dominated by round-trip
    latency."""
    n = x.shape[0]
    nb = num_batches if num_batches is not None else max(1, -(-n // batch_size))
    total = nb * batch_size
    if n > total:
        if not allow_truncate:
            raise ValueError(f"num_batches={nb} too small for {n} samples")
        x, y, n = x[:total], y[:total], total
    pad = total - n
    xp = np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)]) if pad else x
    yp = np.concatenate([y, np.zeros((pad,) + y.shape[1:], y.dtype)]) if pad else y
    mask = np.concatenate([np.ones(n, np.float32), np.zeros(pad, np.float32)])
    return (
        xp.reshape((nb, batch_size) + x.shape[1:]),
        yp.reshape((nb, batch_size) + y.shape[1:]),
        mask.reshape(nb, batch_size),
    )


def pack_one(
    x: np.ndarray,
    y: np.ndarray,
    batch_size: int,
    num_batches: Optional[int] = None,
    x_dtype=jnp.float32,
    y_dtype=None,
    allow_truncate: bool = False,
) -> Batches:
    """Pack one client's samples into [nb, bs, ...] + mask.

    ``allow_truncate``: keep only the first ``num_batches*batch_size``
    samples (used by ``pack_clients`` when the bucketing heuristic caps
    a long-tail client)."""
    xp, yp, mask = _pack_one_np(
        x, y, batch_size, num_batches, allow_truncate=allow_truncate
    )
    if y_dtype is None:
        y_dtype = jnp.int32 if np.issubdtype(y.dtype, np.integer) else jnp.float32
    return Batches(
        x=jnp.asarray(xp, dtype=x_dtype),
        y=jnp.asarray(yp, dtype=y_dtype),
        mask=jnp.asarray(mask),
    )


def pack_clients(
    xs: Sequence[np.ndarray],
    ys: Sequence[np.ndarray],
    batch_size: int,
    num_batches: Optional[int] = None,
    x_dtype=jnp.float32,
) -> Tuple[Batches, jnp.ndarray]:
    """Pack a federation: all clients padded to a common ``num_batches``
    (max over clients unless given) and stacked -> leaves [C, nb, bs, ...].

    Returns (stacked_batches, num_samples[C]). The shared nb is what
    makes the cohort vmap-able; the mask keeps ragged semantics exact.
    """
    if num_batches is None:
        num_batches = max(max(1, -(-len(x) // batch_size)) for x in xs)
    _warn_truncation("pack_clients", [len(x) for x in xs], num_batches, batch_size)
    packed = [
        _pack_one_np(x, y, batch_size, num_batches, allow_truncate=True)
        for x, y in zip(xs, ys)
    ]
    y_dtype = (
        jnp.int32 if np.issubdtype(ys[0].dtype, np.integer) else jnp.float32
    )
    # stack host-side, ONE transfer per leaf (see _pack_one_np)
    stacked = Batches(
        x=jnp.asarray(np.stack([p[0] for p in packed]), dtype=x_dtype),
        y=jnp.asarray(np.stack([p[1] for p in packed]), dtype=y_dtype),
        mask=jnp.asarray(np.stack([p[2] for p in packed])),
    )
    # weights reflect the samples actually packed (long-tail clients may
    # have been truncated to num_batches*batch_size)
    cap = num_batches * batch_size
    num_samples = jnp.asarray(
        [min(len(x), cap) for x in xs], dtype=jnp.float32
    )
    return stacked, num_samples


def pack_labels_np(
    ys: Sequence[np.ndarray],
    batch_size: int,
    num_batches: Optional[int] = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side federation packing of labels only: ``(y[C, nb, bs],
    mask[C, nb, bs], num_samples[C])`` numpy arrays.

    The device-synthesis path (loader._device_synth_classification)
    ships only these few KB to the device and generates the feature
    tensor there — the host never materializes images at all. One
    pad/truncate implementation serves both paths (:func:`_pack_one_np`,
    labels passed in the x slot), so mask/truncation semantics cannot
    drift from :func:`pack_clients`."""
    if num_batches is None:
        num_batches = max(max(1, -(-len(y) // batch_size)) for y in ys)
    _warn_truncation("pack_labels_np", [len(y) for y in ys], num_batches, batch_size)
    packed = [
        _pack_one_np(y, y, batch_size, num_batches, allow_truncate=True)
        for y in ys
    ]
    cap = num_batches * batch_size
    num_samples = np.asarray(
        [min(len(y), cap) for y in ys], dtype=np.float32
    )
    return (
        np.stack([p[0] for p in packed]),
        np.stack([p[2] for p in packed]),
        num_samples,
    )


def _warn_truncation(
    who: str, sizes: List[int], num_batches: int, batch_size: int
) -> None:
    """No silent caps: name what a too-small ``num_batches`` drops and
    the knob that raises it (shared by the image and label packers)."""
    cap = num_batches * batch_size
    truncated = [s - cap for s in sizes if s > cap]
    if truncated:
        dropped = sum(truncated)
        total = sum(sizes)
        logging.warning(
            "%s: long-tail truncation — %d/%d clients exceed "
            "num_batches=%d x batch_size=%d; dropping %d/%d samples "
            "(%.2f%%). Raise args.packing_waste_cap to keep them.",
            who, len(truncated), len(sizes), num_batches, batch_size,
            dropped, total, 100.0 * dropped / max(total, 1),
        )


def bucket_num_batches(sizes: List[int], batch_size: int, waste_cap: float = 4.0) -> int:
    """Heuristic shared nb: cap padding waste by clamping to
    ``waste_cap`` x median client size (huge-client tail gets truncated
    batches dropped rather than blowing up every client's padding).
    ``waste_cap`` is user-facing as ``args.packing_waste_cap``; raising
    it trades padding memory for keeping the long tail's samples
    (``pack_clients`` logs exactly what a given cap drops); ``inf``
    disables truncation entirely."""
    nbs = [max(1, -(-s // batch_size)) for s in sizes]
    med = float(np.median(nbs))
    return int(min(max(nbs), max(1.0, waste_cap * med)))
