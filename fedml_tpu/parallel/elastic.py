"""Elastic-mesh preemption tolerance (ROADMAP: robustness).

TPU fleets lose chips and whole pods mid-run — maintenance events,
spot preemption, or a flaky ICI link — and the only defensible
response is the one this module packages: get DURABLE, get OUT, come
back on whatever devices survived, and prove nothing changed.

The seam has three parts:

1. **Signal** — a pluggable :class:`PreemptionSignal` polled once per
   round at the round boundary (never inside a jit). Sources:
   :class:`SimulatedPreemption` (scripted round trigger, the tests'
   drill), :class:`FilePreemption` (touch a file from another process),
   :class:`MetadataPreemption` (the GCE metadata-server
   ``maintenance-event`` poll on real TPU VMs — stdlib urllib, absent
   server reads as "no event"), and :class:`ChaosPreemption` (the
   chaos plane's ``elastic.check`` event, so ``preempt`` /
   ``device.loss`` faults ride the deterministic schedule machinery).

2. **Drain + durable exit** — on notice the round loop finishes the
   in-flight round (the pipeline drains its depth-K deque through the
   same block-until-ready barrier it already uses before snapshots;
   quorum/partial-close worlds close their round through the existing
   machinery), then :func:`preempt_now` appends a WAL
   ``kind="preempt"`` record WRITE-AHEAD of a forced checkpoint and
   raises :class:`Preempted` — a clean controlled exit, not a crash.
   The WAL order matters: a preempt record without its checkpoint is
   detectable (invariants: ``preempt_paired_with_checkpoint``), the
   reverse — a checkpoint whose reason for existing was lost — is not.

3. **Reshaped resume** — the restart passes the *surviving* device set
   to :func:`build_fed_mesh` (``surviving_mesh``), restores the
   checkpoint device-direct onto the new layout via ``restore_target``
   NamedShardings, and reshards any in-flight streaming-accumulator
   state with :func:`reshape_limb_state`: limbs travel through
   ``export_state``/``fold_limbs``, so every fold that happened before
   the preemption is carried exactly once — never re-applied, never
   lost — across the mesh reshape. PR 15's mesh-shape bit-identity
   (every ``(data, fsdp)`` shape finalizes bitwise equal to
   single-chip) then guarantees the resumed run's final params are
   bitwise identical to an uninterrupted run: tests/test_elastic_mesh.py
   (``TestPreemptResume``) holds that at 8->4 forced devices.

Counters: ``elastic_preemptions_total`` (on the preempt path) and
``elastic_resumes_total`` (on a resume that consumed a preempt WAL
record).
"""

from __future__ import annotations

import logging
import time
from typing import Any, Dict, List, Optional, Sequence

from .layout import build_fed_mesh, is_fed_mesh, shard_tree

__all__ = [
    "PreemptionNotice",
    "Preempted",
    "PreemptionSignal",
    "SimulatedPreemption",
    "FilePreemption",
    "MetadataPreemption",
    "ChaosPreemption",
    "make_signal",
    "surviving_mesh",
    "reshape_limb_state",
    "preempt_now",
]


class PreemptionNotice:
    """An impending-eviction notice: why, and whatever the source knew.

    ``detail`` is schema-free source context (the metadata event body,
    the chaos fault step, the trigger round) — it rides into the WAL
    record's ``extra`` block verbatim, so a post-mortem can tell a
    scripted drill from a real maintenance event.
    """

    def __init__(self, reason: str, detail: Optional[Dict[str, Any]] = None):
        self.reason = str(reason)
        self.detail = dict(detail or {})

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return f"PreemptionNotice(reason={self.reason!r}, detail={self.detail!r})"


class Preempted(RuntimeError):
    """Clean controlled exit after a drained round + durable state.

    Raised by :func:`preempt_now` AFTER the WAL preempt record and the
    forced checkpoint are durable — the catcher (a test harness, a real
    launcher's supervisor) may exit the process knowing a restart on
    the surviving devices resumes bitwise-identically.
    """

    def __init__(self, notice: PreemptionNotice, round_idx: int, ckpt_step: int):
        self.notice = notice
        self.round_idx = int(round_idx)
        self.ckpt_step = int(ckpt_step)
        super().__init__(
            f"preempted ({notice.reason}) after round {round_idx}; "
            f"checkpoint step {ckpt_step} is durable — restart on the "
            "surviving devices to resume"
        )


class PreemptionSignal:
    """Base seam: ``poll(round_idx)`` -> notice or None.

    Polled at the ROUND BOUNDARY only — after the round's fold is
    finalized and any cadence checkpoint has fired — so a notice never
    tears a round: the drain semantics are "finish what is in flight,
    then leave".
    """

    def poll(self, round_idx: int) -> Optional[PreemptionNotice]:
        raise NotImplementedError

    def describe(self) -> str:
        return type(self).__name__


class SimulatedPreemption(PreemptionSignal):
    """Scripted maintenance-event drill: fires once ``round_idx``
    reaches ``at_round``. The tests' mid-run trigger."""

    def __init__(self, at_round: int, reason: str = "maintenance-simulated"):
        self.at_round = int(at_round)
        self.reason = str(reason)

    def poll(self, round_idx: int) -> Optional[PreemptionNotice]:
        if int(round_idx) >= self.at_round:
            return PreemptionNotice(
                self.reason, {"at_round": self.at_round, "round": int(round_idx)}
            )
        return None

    def describe(self) -> str:
        return f"round:{self.at_round}"


class FilePreemption(PreemptionSignal):
    """Fires when ``path`` exists — the cross-process scripting seam
    (an external supervisor touches the file to request drain)."""

    def __init__(self, path: str):
        self.path = str(path)

    def poll(self, round_idx: int) -> Optional[PreemptionNotice]:
        import os

        if os.path.exists(self.path):
            return PreemptionNotice(
                "preempt-file", {"path": self.path, "round": int(round_idx)}
            )
        return None

    def describe(self) -> str:
        return f"file:{self.path}"


class MetadataPreemption(PreemptionSignal):
    """GCE metadata-server maintenance-event poll (real TPU VMs).

    ``http://metadata.google.internal/computeMetadata/v1/instance/
    maintenance-event`` returns ``NONE`` between events and
    ``TERMINATE_ON_HOST_MAINTENANCE`` (or similar) when eviction is
    scheduled. Off-GCE the server is unreachable: that reads as "no
    event", never an error — the signal must not add a failure mode.
    Stdlib urllib only; no new dependencies.
    """

    URL = (
        "http://metadata.google.internal/computeMetadata/v1/"
        "instance/maintenance-event"
    )

    def __init__(self, timeout_s: float = 1.0):
        self.timeout_s = float(timeout_s)

    def poll(self, round_idx: int) -> Optional[PreemptionNotice]:
        import urllib.error
        import urllib.request

        req = urllib.request.Request(
            self.URL, headers={"Metadata-Flavor": "Google"}
        )
        try:
            with urllib.request.urlopen(req, timeout=self.timeout_s) as resp:
                body = resp.read().decode("utf-8", "replace").strip()
        except (urllib.error.URLError, OSError, ValueError):
            return None  # off-GCE / transient: no event
        if body and body.upper() != "NONE":
            return PreemptionNotice(
                "maintenance-event", {"event": body, "round": int(round_idx)}
            )
        return None

    def describe(self) -> str:
        return "metadata"


class ChaosPreemption(PreemptionSignal):
    """Bridge from the deterministic chaos plane: a ``preempt`` or
    ``device.loss`` fault scheduled on the ``elastic.check`` event
    becomes a notice — drills ride the same reproducible
    (ChaosSchedule, seed) machinery as every other fault."""

    def poll(self, round_idx: int) -> Optional[PreemptionNotice]:
        from ..core.chaos import elastic_event

        fault = elastic_event(int(round_idx))
        if fault is None:
            return None
        return PreemptionNotice(
            str(fault.get("kind", "preempt")),
            {"chaos_fault": dict(fault), "round": int(round_idx)},
        )

    def describe(self) -> str:
        return "chaos"


def make_signal(spec) -> Optional[PreemptionSignal]:
    """Parse the ``preempt_signal`` knob into a signal source.

    ``None``/``""``/``"none"`` -> no signal; ``"round:K"`` ->
    :class:`SimulatedPreemption`; ``"file:/path"`` ->
    :class:`FilePreemption`; ``"metadata"`` ->
    :class:`MetadataPreemption`; ``"chaos"`` ->
    :class:`ChaosPreemption`. Anything else is a loud ValueError —
    a misspelled signal must not run signal-free.
    """
    if spec is None or isinstance(spec, PreemptionSignal):
        return spec
    s = str(spec).strip()
    if not s or s.lower() == "none":
        return None
    if s.startswith("round:"):
        raw = s[len("round:"):]
        try:
            at = int(raw)
        except (TypeError, ValueError):
            raise ValueError(
                f"preempt_signal={spec!r}: 'round:K' needs an integer "
                "round index"
            ) from None
        if at < 0:
            raise ValueError(
                f"preempt_signal={spec!r}: round index must be >= 0"
            )
        return SimulatedPreemption(at)
    if s.startswith("file:"):
        path = s[len("file:"):]
        if not path:
            raise ValueError(
                f"preempt_signal={spec!r}: 'file:PATH' needs a path"
            )
        return FilePreemption(path)
    if s == "metadata":
        return MetadataPreemption()
    if s == "chaos":
        return ChaosPreemption()
    raise ValueError(
        f"preempt_signal={spec!r}: expected none | round:K | file:PATH "
        "| metadata | chaos"
    )


def surviving_mesh(
    devices: Optional[Sequence] = None,
    mesh_shape: Optional[dict] = None,
    *,
    min_devices: int = 1,
):
    """Build the fed mesh over the devices that survived.

    The restart-world entry point: pass the surviving device list (or
    None for all currently-visible devices) and the reshaped
    ``mesh_shape``. ``min_devices`` (the ``elastic_min_devices`` knob)
    is the floor below which resuming is refused LOUDLY — below it the
    operator wants a page, not a crawl.
    """
    import jax

    devices = list(devices if devices is not None else jax.devices())
    floor = max(1, int(min_devices))
    if len(devices) < floor:
        raise RuntimeError(
            f"elastic resume refused: {len(devices)} surviving devices "
            f"< elastic_min_devices={floor} — not enough capacity to "
            "continue; restore on a bigger slice or lower the floor"
        )
    return build_fed_mesh(devices=devices, mesh_shape=mesh_shape)


def reshape_limb_state(state: Dict[str, Any], mesh) -> Dict[str, Any]:
    """Re-place exported streaming-accumulator limbs onto ``mesh``.

    ``state`` is ``StreamingAccumulator.export_state()`` — three
    host-numpy limb trees plus exact host-float ``total_w`` and int
    ``count``. Each limb is placed fsdp-sharded at rest on the new
    mesh (the same ``shard_tree`` placement params get); feeding the
    result to ``fold_limbs`` on a fresh accumulator carries every
    pre-preemption fold across the reshape bitwise — the limbs ARE the
    fold history, and ``fold_limbs`` re-folds each one exactly once
    through the same two-sum executable regardless of placement.
    """
    if mesh is None or not is_fed_mesh(mesh):
        return state
    out = dict(state)
    out["limbs"] = [shard_tree(limb, mesh) for limb in state["limbs"]]
    return out


def _mesh_devices(mesh) -> List[str]:
    if mesh is None:
        return []
    try:
        return [str(d) for d in mesh.devices.flatten()]
    except Exception:  # pragma: no cover - exotic mesh impls
        return []


def _mesh_shape(mesh) -> Dict[str, int]:
    """JSON-safe ``{axis: size}`` of a mesh (WAL extra blocks)."""
    if mesh is None:
        return {}
    try:
        return {str(k): int(v) for k, v in dict(mesh.shape).items()}
    except Exception:  # pragma: no cover - exotic mesh impls
        return {}


def preempt_now(
    api, ckpt, round_idx: int, notice: PreemptionNotice, *, saved: bool = False
) -> None:
    """Durable exit: WAL ``kind="preempt"`` write-ahead, forced
    checkpoint, then raise :class:`Preempted`.

    Called at the round boundary AFTER round ``round_idx`` fully
    drained (its fold finalized into ``api.global_params``). The WAL
    record lands BEFORE the checkpoint publish — the invariant checker
    pairs every preempt record with the checkpoint it promises
    (``preempt_paired_with_checkpoint``), so a crash between the two
    writes is detectable from artifacts. ``saved=True`` skips the
    forced save when the cadence block already published this round's
    step (the double-save would be wasted IO, not a correctness bug).
    """
    from ..core.checkpoint import RoundWAL

    if ckpt is None:
        raise RuntimeError(
            "preemption notice with no checkpointer: set checkpoint_dir "
            "so the drained round can be made durable before exiting"
        )
    mesh = getattr(api, "mesh", None)
    wal = RoundWAL(ckpt.dir)
    extra = {
        "reason": notice.reason,
        "devices": _mesh_devices(mesh),
        "mesh_shape": _mesh_shape(mesh),
        **notice.detail,
    }
    wal.append(
        int(round_idx), int(round_idx), [], kind="preempt", extra=extra
    )
    if not saved:
        api._save_checkpoint(ckpt, int(round_idx))
    tel = getattr(api, "telemetry", None)
    if tel is not None and getattr(tel, "enabled", False):
        tel.inc("elastic_preemptions_total")
    logging.warning(
        "preemption (%s): round %d drained, checkpoint step %d durable "
        "— exiting cleanly; resume on the surviving devices",
        notice.reason, int(round_idx), int(round_idx),
    )
    raise Preempted(notice, int(round_idx), int(round_idx))


