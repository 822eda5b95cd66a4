"""Beehive world driver: one whole cross-device federation, in process.

``run_beehive_world`` stands up the two-rank LOCAL fabric (gateway +
device population), runs ``args.comm_round`` check-in rounds end to
end, exports telemetry artifacts (so ``InvariantChecker`` can audit
the run offline against the RoundWAL it wrote), tears the fabric down,
and returns a plain dict of results — final params, per-round close
records, and the compile census. The tests (tests/test_beehive.py) and the
``fedml-tpu device`` CLI smoke both enter here;
nothing about the protocol lives in this file.
"""

from __future__ import annotations

import copy
import threading
from typing import Any, Dict, Optional

import numpy as np

from ..core.telemetry import Telemetry
from ..scale.registry import ClientRegistry
from .device import DeviceHost
from .gateway import DeviceGateway

__all__ = ["run_beehive_world"]

# generous per-rank join bound: a wedged protocol should fail loudly,
# not hang the suite
_JOIN_TIMEOUT_S = 300.0


def run_beehive_world(
    args,
    *,
    feature_dim: int = 8,
    class_num: int = 4,
    registry: Optional[ClientRegistry] = None,
) -> Dict[str, Any]:
    """Run a full Beehive federation and return its observable state.

    Returns ``final_flat`` / ``final_params`` (the gateway's global
    model), ``round_records`` (close reason, fold target, folds,
    recoveries per round), ``trace_count`` / ``shape_keys`` (the
    device plane's compile census), and ``registry_size``.
    """
    a = copy.copy(args)
    a.run_id = f"{getattr(args, 'run_id', '0')}-beehive"
    if registry is None:
        size = int(getattr(a, "client_registry_size", 0) or 0) or 10_000
        registry = ClientRegistry(
            size,
            seed=int(getattr(a, "random_seed", 0) or 0),
            duty_hours=int(getattr(a, "crossdevice_duty_hours", 14)),
        )
    # fallback chain mirrors the planet plane: the registry-mode
    # cohort_size knob (validated against client_registry_size), then
    # the classic per-round count
    cohort = (
        int(getattr(a, "crossdevice_cohort", 0) or 0)
        or int(getattr(a, "cohort_size", 0) or 0)
        or int(getattr(a, "client_num_per_round", 4))
    )
    rounds = int(getattr(a, "comm_round", 1))
    gateway = DeviceGateway(
        a, registry, feature_dim, class_num, rounds, cohort
    )
    host = DeviceHost(
        a, registry, feature_dim, class_num, rounds, cohort
    )
    threads = [
        threading.Thread(
            target=gateway.run, name="beehive-gateway", daemon=True
        ),
        threading.Thread(
            target=host.run, name="beehive-devices", daemon=True
        ),
    ]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=_JOIN_TIMEOUT_S)
        wedged = [t.name for t in threads if t.is_alive()]
        if wedged:
            raise RuntimeError(
                f"beehive world wedged after {_JOIN_TIMEOUT_S}s: {wedged} "
                "still running (protocol deadlock — see the round ledger "
                "in the RoundWAL for the last close)"
            )
    finally:
        # artifacts BEFORE teardown: the invariant checker reads the
        # exported counter snapshot next to the WAL even on failure
        Telemetry.get_instance().export_run_artifacts(
            getattr(a, "telemetry_dir", None)
        )
        gateway.com_manager.stop_receive_message()
        host.com_manager.stop_receive_message()
        inner = gateway.com_manager
        while not hasattr(inner, "destroy_fabric") and hasattr(inner, "inner"):
            inner = inner.inner
        if hasattr(inner, "destroy_fabric"):
            inner.destroy_fabric()
    return {
        "final_flat": np.asarray(gateway.global_flat, dtype=np.float64),
        "final_params": gateway.global_params,
        "round_records": list(gateway.round_records),
        "trace_count": int(host.trace_count),
        "shape_keys": sorted(host.shape_keys),
        "registry_size": int(registry.size),
    }
