"""System-resource sampling (SysStats parity).

Reference: ``core/mlops/system_stats.py:8-60`` samples CPU/mem/disk/net
(+GPU via pynvml) through wandb's SystemStats and ships them to the
MLOps platform. Here: direct psutil sampling (no wandb dependency) plus
TPU-side memory stats from the JAX runtime when available; records go
to the same pluggable-sink ``MetricsReporter`` the rest of the
framework uses.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Dict, Optional

try:
    import psutil

    _HAS_PSUTIL = True
except ImportError:  # pragma: no cover
    _HAS_PSUTIL = False


def current_rss_bytes() -> int:
    """This process's resident set size right now (0 only when
    unmeasurable: no psutil AND no /proc). tests/test_planet_scale.py
    differences this around a 1M-registry round to hold the
    O(cohort)-not-O(registry) host-memory claim."""
    if _HAS_PSUTIL:
        return int(psutil.Process().memory_info().rss)
    try:  # psutil-less Linux: statm field 2 is resident page count
        import os

        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGESIZE")
    except (OSError, ValueError, IndexError):  # pragma: no cover
        return 0


def cpu_steal_ticks() -> Optional[int]:
    """The ``steal`` column of ``/proc/stat``'s ``cpu`` line: clock
    ticks, summed over this machine's CPUs since boot, that the
    hypervisor gave to someone else. The round loops record its
    difference from round to round (``ProfilerEvent.iteration_span``) so a
    stalled round can be told from a starved one. None off Linux."""
    try:
        with open("/proc/stat", "rb") as f:
            fields = f.readline().split()
        return int(fields[8])
    except (OSError, ValueError, IndexError):
        return None


def sample_host_stats() -> Dict[str, Any]:
    """One snapshot of host CPU/memory/disk/net counters."""
    if not _HAS_PSUTIL:
        return {}
    vm = psutil.virtual_memory()
    disk = psutil.disk_usage("/")
    net = psutil.net_io_counters()
    return {
        "cpu_util_pct": psutil.cpu_percent(interval=None),
        "mem_used_gb": vm.used / 2**30,
        "mem_util_pct": vm.percent,
        "disk_util_pct": disk.percent,
        "net_sent_mb": net.bytes_sent / 2**20,
        "net_recv_mb": net.bytes_recv / 2**20,
        "proc_rss_gb": psutil.Process().memory_info().rss / 2**30,
    }


# one debug line per process when a backend has no memory stats — not
# one per 10s sampling tick
_DEVICE_STATS_LOGGED = False


def _log_device_stats_unavailable(why: str) -> None:
    global _DEVICE_STATS_LOGGED
    if not _DEVICE_STATS_LOGGED:
        logging.debug("device memory stats unavailable: %s", why)
        _DEVICE_STATS_LOGGED = True


def sample_device_stats() -> Dict[str, Any]:
    """Accelerator memory stats from the JAX runtime (the GPU/pynvml
    analog for TPU devices); empty when the backend has none.
    ``bytes_limit`` is exported alongside ``bytes_in_use`` so HBM
    headroom is a gauge, not a ratio the operator must reconstruct."""
    try:
        import jax

        devices = jax.local_devices()
    except (ImportError, RuntimeError) as e:  # backend init failed
        _log_device_stats_unavailable(f"{type(e).__name__}: {e}")
        return {}
    stats: Dict[str, Any] = {}
    for i, dev in enumerate(devices):
        try:
            ms = getattr(dev, "memory_stats", lambda: None)()
        except (RuntimeError, NotImplementedError, AttributeError) as e:
            # the CPU backend (and some TPU runtimes) has no stats —
            # expected, not an error worth hiding everything behind
            _log_device_stats_unavailable(f"{dev}: {type(e).__name__}: {e}")
            continue
        if ms:
            stats[f"device{i}_bytes_in_use"] = ms.get("bytes_in_use", 0)
            stats[f"device{i}_peak_bytes"] = ms.get("peak_bytes_in_use", 0)
            if "bytes_limit" in ms:
                stats[f"device{i}_bytes_limit"] = ms["bytes_limit"]
    return stats


class SysStats:
    """Background sampler publishing to a reporter every ``interval_s``
    (system_stats.py's sampling loop, minus the wandb indirection)."""

    def __init__(self, reporter, interval_s: float = 10.0, telemetry=None) -> None:
        self.reporter = reporter
        self.interval_s = float(interval_s)
        self.telemetry = telemetry  # optional Telemetry: samples as gauges
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "SysStats":
        if self._thread is not None:
            return self
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        return self

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                rec = {"kind": "sys_stats", **sample_host_stats(), **sample_device_stats()}
                self.reporter.report(rec)
                if self.telemetry is not None:
                    self.telemetry.set_system_gauges(rec)
            except Exception:  # pragma: no cover
                logging.exception("sys stats sampling failed")

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=self.interval_s + 1)
            self._thread = None
