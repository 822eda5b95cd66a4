"""Per-auditable-executable device-time accounting.

Every ``auditable(...)`` call site wraps its dispatch in
:func:`measure`, which brackets the call two ways at once (a
``jax.named_scope`` round the *call* of an already-jitted function
reaches no HLO and no trace, so there is none here; the scopes that do
are inside the traced functions, ``simulation/fedavg_api.build_round_fn``):

* a flight-recorder B/E span (``cat="exec"``) so the offline trace
  stitcher sees exactly where each executable sat on the round's
  critical path;
* an ``exec_device_seconds{executable,bucket}`` histogram observation
  plus an entry in a bounded wall-clock ring, which is what
  ``fedml-tpu perf`` joins against the audit roofline.

The wall-clock caveat is deliberate and documented
(docs/observability.md): round executables are *async dispatches*, so
a single call's wall time is dispatch time, not device time. With
donated-carry chains the next dispatch back-pressures on the previous
round's result, so in steady state per-call wall time converges on
device time; ``serving.forward`` wraps the dispatch *and* its single
``np.asarray`` fetch, so its measurement is true device+transfer time.

The hot-loop contract (tests/test_telemetry.py: ``host_syncs_per_round``
bit-identical with telemetry on/off) means this module must never add
a device fetch or block — it is ``perf_counter`` reads and dict/deque
updates only.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, Iterator, List, Optional

from .telemetry import Telemetry

# ring default; runs override via the ``devtime_ring_size`` knob
# (adopted lazily, same late-rebind pattern as ``trace_ring_size``)
DEFAULT_RING_SIZE = 4096

# histogram bounds: dispatches are sub-ms on CPU smoke, whole rounds
# reach tens of seconds on real federations
_BUCKETS = (1e-4, 1e-3, 5e-3, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 30.0)

_lock = threading.Lock()
_ring: deque = deque(maxlen=DEFAULT_RING_SIZE)
_adopted_ring_size: Optional[int] = None
# monotonic origin so ring timestamps order without wall-clock reads
_T0 = time.perf_counter()


def configure(args) -> None:
    """Adopt ``devtime_ring_size`` (idempotent; existing entries kept
    up to the new capacity, newest-first — same contract as
    ``FlightRecorder.resize``)."""
    global _ring, _adopted_ring_size
    size = getattr(args, "devtime_ring_size", None)
    if not size:
        return
    size = int(size)
    with _lock:
        if size == _adopted_ring_size:
            return
        _ring = deque(_ring, maxlen=max(1, size))
        _adopted_ring_size = size


def reset() -> None:
    """Drop accumulated state (tests)."""
    global _ring, _adopted_ring_size
    with _lock:
        _ring = deque(maxlen=DEFAULT_RING_SIZE)
        _adopted_ring_size = None


def ring_snapshot() -> List[Dict[str, Any]]:
    """The wall-clock fallback ring, oldest first. Each entry:
    ``{executable, bucket, seconds, t_rel}`` with ``t_rel`` seconds
    since process devtime origin (monotonic, NOT wall clock)."""
    with _lock:
        return list(_ring)


@contextmanager
def measure(executable: str, bucket: Optional[str] = None) -> Iterator[None]:
    """Bracket one dispatch of a registered auditable executable.

    Zero device fetches: ``perf_counter`` + in-memory updates only.
    The ring records even with telemetry disabled (it IS the
    fallback); histogram/trace emission is telemetry-gated."""
    tel = Telemetry.get_instance()
    if tel.args is not None:
        configure(tel.args)
    enabled = tel.enabled
    tags: Dict[str, str] = {"executable": executable}
    if bucket is not None:
        tags["bucket"] = str(bucket)
    name = f"exec.{executable}"
    if enabled:
        tel.recorder.begin(name, cat="exec", **tags)
    t0 = time.perf_counter()
    try:
        yield
    finally:
        dt = time.perf_counter() - t0
        if enabled:
            tel.recorder.end(name, cat="exec", **tags)
            tel.observe("exec_device_seconds", dt, buckets=_BUCKETS, **tags)
        with _lock:
            _ring.append(
                {
                    "executable": executable,
                    "bucket": None if bucket is None else str(bucket),
                    "seconds": dt,
                    "t_rel": t0 - _T0,
                }
            )


def measured_executables() -> List[str]:
    """Distinct executable names seen by the ring (debug/watch UIs)."""
    with _lock:
        return sorted({e["executable"] for e in _ring})
