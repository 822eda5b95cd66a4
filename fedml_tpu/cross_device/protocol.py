"""Beehive check-in protocol: wire payloads shared by gateway and device.

The connectionless cross-device plane (docs/cross_device.md) speaks a
seven-message protocol over the comm seam (``core/managers``): devices
check in, pull the round offer (int8-codec global params + the
participant roster), push one masked delta, and disappear. This module
owns everything BOTH ends must agree on byte-for-byte:

- the linear device model template and its flat field layout (the
  pairwise masks live on the flattened update, so gateway and device
  must flatten in the identical leaf order — ``flatten_params``'s);
- the int8 offer codec (``core/compression.Int8Codec``): the offer is
  lossy by design, and BOTH the masked and unmasked worlds train from
  the same decoded tree, which is one of the two legs of the bitwise
  masked==unmasked identity tests/test_beehive.py proves;
- participant-roster and share-reveal payload packing (numpy columns,
  msgpack-clean — no pickled objects cross the seam).

Server-side per-device state is bounded by construction: a roster is a
pair of int64 columns, a reveal is a (point, value) table, and nothing
here references a live device object.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

Params = Any

__all__ = [
    "linear_template",
    "flat_dim",
    "encode_offer_params",
    "decode_offer_params",
    "pack_participants",
    "unpack_participants",
    "pack_reveals",
    "unpack_reveals",
]


# -- device model ----------------------------------------------------------


def linear_template(feature_dim: int, class_num: int) -> Params:
    """The device-side model: one linear softmax classifier. Zeros are
    the canonical cold start — every world (masked, unmasked, CLI
    smoke) begins from the identical params, so final-params
    comparisons need no init plumbing."""
    return {
        "b": np.zeros((int(class_num),), np.float32),
        "w": np.zeros((int(feature_dim), int(class_num)), np.float32),
    }


def flat_dim(feature_dim: int, class_num: int) -> int:
    """Length of the flattened update vector the field math runs on."""
    return int(feature_dim) * int(class_num) + int(class_num)


# -- offer codec (int8 over the wire) --------------------------------------


def encode_offer_params(params: Params) -> Params:
    """Global params -> int8 wire tree (host numpy leaves)."""
    import jax

    from ..core.compression import Int8Codec

    return jax.tree.map(np.asarray, Int8Codec.encode(params))


def decode_offer_params(encoded: Params) -> Params:
    """int8 wire tree -> float32 params (host numpy leaves)."""
    import jax

    from ..core.compression import Int8Codec

    return jax.tree.map(np.asarray, Int8Codec.decode(encoded))


# -- participant roster ----------------------------------------------------


def pack_participants(participants: Dict[int, int]) -> Dict[str, np.ndarray]:
    """{device_id: mask pubkey} -> two aligned int64 columns, sorted by
    device id. The SORTED order is normative: Shamir share points are
    positions in this roster (device at position k holds point k+1), so
    both ends must derive the identical ordering from the payload."""
    ids = np.fromiter(sorted(participants), dtype=np.int64)
    pubs = np.asarray([participants[int(i)] for i in ids], dtype=np.int64)
    return {"ids": ids, "pubs": pubs}


def unpack_participants(payload: Dict[str, np.ndarray]) -> Dict[int, int]:
    ids = np.asarray(payload["ids"], dtype=np.int64)
    pubs = np.asarray(payload["pubs"], dtype=np.int64)
    return {int(i): int(p) for i, p in zip(ids, pubs)}


# -- share reveals ---------------------------------------------------------


def pack_reveals(
    reveals: Dict[int, List[Tuple[int, int]]]
) -> Dict[str, np.ndarray]:
    """{vanished_id: [(point, share_value), ...]} -> one flat int64
    table [n, 3] of (vanished_id, point, value) rows (str-keyed nested
    dicts of variable length are msgpack-hostile; a column table is
    not)."""
    rows = [
        (int(v), int(point), int(val))
        for v, pairs in sorted(reveals.items())
        for point, val in pairs
    ]
    return {
        "table": np.asarray(rows, dtype=np.int64).reshape(len(rows), 3)
    }


def unpack_reveals(
    payload: Dict[str, np.ndarray]
) -> Dict[int, List[Tuple[int, int]]]:
    out: Dict[int, List[Tuple[int, int]]] = {}
    for v, point, val in np.asarray(payload["table"], dtype=np.int64):
        out.setdefault(int(v), []).append((int(point), int(val)))
    return out
