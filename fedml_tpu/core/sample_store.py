"""The round executable's sample store: the packed federation with every
sample flattened to one axis, so that a client is one contiguous block
of device memory.

Why it exists (PERF.md §6, PR 27). A TPU lays an array out by the
dimensions that pad least, and for image-shaped samples
``bf16[100, 15, 64, 32, 32, 3]`` that makes the *client* axis the 128
lanes of every tile: one client's samples are one lane of the whole
store, and gathering a cohort of 32 read and rewrote the store once per
client (487 ms a round for 189 MB of data). Held as ``[N, nb, B, F]``
with ``F = prod(sample shape)`` the same samples lie client-major, the
cohort is ``C`` contiguous copies, and the round executable reshapes
what it gathered back to the sample's shape
(``simulation/fedavg_api.build_round_fn``).

The layout is the compiler's choice, not a property of flatness
(``bf16[1000, 15, 64, 784]`` comes out client-minor flat or not), so
:func:`stage` reads back what it got and pins the client axis
most-major where the default is not.
"""

from __future__ import annotations

import logging
import math
from typing import Any, Dict, Optional, Tuple

import jax

from .types import Batches


def sample_shape(packed: Optional[Batches]) -> Optional[Tuple[int, ...]]:
    """The shape the round executable restores on the gathered cohort's
    samples, or None where a sample is one-dimensional already (token
    ids, tabular rows, the audit's abstract batches): those are gathered
    from the dataset's own arrays and nothing is reshaped."""
    if packed is None:
        return None
    shape = tuple(int(d) for d in packed.x.shape[packed.mask.ndim:])
    return shape if len(shape) > 1 else None


def _major_to_minor(x: jax.Array) -> Optional[Tuple[int, ...]]:
    """The array's on-device dimension order, most-major first, where
    the backend reports one."""
    layout = x.format.layout
    return None if layout is None else tuple(layout.major_to_minor)


def _flatten(x: jax.Array, shape: Tuple[int, ...], pin: bool) -> jax.Array:
    """One jitted reshape. A mesh-placed federation keeps its spec (it
    names leading axes only, which the reshape keeps); ``pin`` asks for
    row-major by an explicit format and leaves the tiling to the chip."""
    from jax.experimental.layout import Format, Layout
    from jax.sharding import NamedSharding

    out = x.sharding if pin or isinstance(x.sharding, NamedSharding) else None
    if pin:
        out = Format(Layout(tuple(range(len(shape)))), out)
    return jax.jit(lambda a: a.reshape(shape), out_shardings=out)(x)


def stage(packed: Batches) -> Tuple[Batches, Dict[str, Any]]:
    """``(store, facts)`` for ``packed``: ``x`` as ``[N, nb, B, F]``,
    ``y`` and ``mask`` shared. ``facts`` are the ``store.staged``
    instant's arguments (docs/observability.md). The source is never
    donated: evaluation and the sequential loop keep reading it."""
    sample = sample_shape(packed)
    copied = sample is not None
    x = packed.x
    if copied:
        flat = tuple(x.shape[:packed.mask.ndim]) + (math.prod(sample),)
        x = _flatten(packed.x, flat, pin=False)
        order = _major_to_minor(x)
        if order is not None and order[0] != 0:
            del x  # the pinned copy takes its place, not a third one beside it
            try:
                x = _flatten(packed.x, flat, pin=True)
            except (ValueError, NotImplementedError, jax.errors.JaxRuntimeError) as e:
                logging.warning("sample store: no pinned layout for %s: %s", flat, e)
                x = _flatten(packed.x, flat, pin=False)
    order = _major_to_minor(x)
    if order is not None and order[0] != 0:
        logging.warning(
            "sample store %s%s lies %s on the device (most-major first): "
            "the client axis is not the most-major one, so gathering a "
            "cohort reads the whole store once per client",
            x.dtype, list(x.shape), list(order))
    facts = {
        "bytes": int(x.nbytes),
        "shape": [int(d) for d in x.shape],
        "major_to_minor": "unknown" if order is None else list(order),
        "copied": copied,
    }
    return (packed.replace(x=x) if copied else packed), facts
