"""The parts of the benchmark every family shares.

Everything that belongs to one configuration, one traffic mix, one
per-layer metric or one model family sits in a file of its own, found
here by the name ``BENCHMARK.json`` gives it:

- ``configs/<config>.json``      sizes as run, source, reduced, assumed
- ``workloads/<cell>.json``      the traffic mix: parameters only
- ``families/<family>.py``       drives one kind of program entry
- ``reference/<name>.py``        the plain reference a family compares with
- ``flops/<config>.py``          operations and bytes from shapes (a configuration may
                                 name another's under ``flops``)
- ``layer_metrics/<metric>.py``  one reader per per-layer metric
- ``peaks.json``                 the chip's published peaks by device_kind

so a later PR adds a cell, a configuration or a metric by adding files
and ``BENCHMARK.json`` entries, and never edits a file that is there.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)


class BenchError(Exception):
    """The benchmark cannot produce a result (exit code 2, no line)."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import a file by path (metric names may hold dots)."""
    if not os.path.isfile(path):
        raise BenchError(f"no such file: {path}")
    name = "bench_" + "".join(c if c.isalnum() else "_" for c in os.path.abspath(path))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with its files resolved.

    ``root`` is the checkout: ``BENCHMARK.json`` sits there and every
    directory of its ``paths`` is searched, in order, for the files a
    name stands for."""

    def __init__(self, workload: str, root: Optional[str] = None) -> None:
        self.root = root or CHECKOUT
        self.spec = load_json(os.path.join(self.root, "BENCHMARK.json"))
        self.dirs = [os.path.join(self.root, p) for p in self.spec["paths"]]
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if workload not in cells:
            raise BenchError(f"workload {workload!r} is not in BENCHMARK.json: {sorted(cells)}")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in self.spec["configs"]}[self.entry["config"]]
        self.config = load_json(os.path.join(self.root, cfg_entry["file"]))
        self.config_name = cfg_entry["name"]
        self.traffic = load_json(self.find("workloads", self.entry["traffic"] + ".json"))
        self.family = self.config["family"]

    def find(self, kind: str, filename: str) -> str:
        """The first ``<path>/<kind>/<filename>`` over the benchmark's
        directories."""
        for d in self.dirs:
            path = os.path.join(d, kind, filename)
            if os.path.isfile(path):
                return path
        raise BenchError(f"no {kind}/{filename} under {self.spec['paths']}")

    def _metrics(self, group: str) -> List[dict]:
        return [
            m for m in self.spec[group]
            if "workloads" not in m or self.name in m["workloads"]
        ]

    @property
    def end_to_end(self) -> List[dict]:
        return self._metrics("end_to_end")

    @property
    def per_layer(self) -> List[dict]:
        return self._metrics("per_layer")

    def module(self, kind: str, name: str):
        path = self.find(kind, name + ".py")
        if os.path.dirname(path) not in sys.path:
            sys.path.append(os.path.dirname(path))  # a kind's shared helpers
        return load_module(path)

    def family_module(self):
        return self.module("families", self.family)

    def flops_module(self):
        # a configuration of an architecture that is already counted
        # names that file; otherwise its own
        return self.module("flops", self.config.get("flops", self.config_name))

    def reader(self, metric: str):
        return self.module("layer_metrics", metric)


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The published peaks of one chip. An unknown kind is an error,
    never a default."""
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))["chips"]
    if device_kind not in table:
        raise BenchError(
            f"device_kind {device_kind!r} is not in benchmark/peaks.json "
            f"(known: {sorted(table)}); add it with its source")
    return table[device_kind]


def percentile_nearest_rank(values: List[float], q: float) -> float:
    """Nearest-rank percentile of all the values (q in (0, 1])."""
    if not values:
        raise BenchError("percentile of nothing")
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def device_facts(devices) -> Dict[str, Any]:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }


_COMPILES = {"n": 0, "listening": False}
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def compile_count() -> int:
    """Executables this process has built since the first call here,
    whether XLA compiled them or the persistent cache served them: one
    of JAX's backend-compile events each. The first call starts the
    count."""
    if not _COMPILES["listening"]:
        from jax import monitoring

        def on_duration(event: str, duration: float, **kwargs) -> None:
            if event == _COMPILE_EVENT:
                _COMPILES["n"] += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        _COMPILES["listening"] = True
    return _COMPILES["n"]


def worst_leaf_gap(prog_norms, ref_norms) -> float:
    """The training bullet's measure: the gap between the program's
    norm and the reference's (not the norm of their difference), by the
    worst leaf, against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    import numpy as np

    p = np.asarray(prog_norms, np.float64)
    r = np.asarray(ref_norms, np.float64)
    if p.shape != r.shape or p.size == 0:
        raise BenchError(f"leaf norms do not line up: {p.shape} vs {r.shape}")
    if not (np.isfinite(p).all() and np.isfinite(r).all()):
        return float("inf")
    denom = np.maximum(r, np.median(r))
    denom = np.where(denom > 0, denom, 1.0)
    return float(np.max(np.abs(p - r) / denom))


def rel_gap(a: float, b: float) -> float:
    if not (math.isfinite(a) and math.isfinite(b)):
        return float("inf")
    return abs(a - b) / max(abs(b), 1e-30)


class Compared:
    """The numbers a run compares, each beside its limit."""

    def __init__(self) -> None:
        self.rows: List[dict] = []

    def add(self, name: str, value: float, limit: float) -> None:
        ok = bool(math.isfinite(value) and value <= limit)
        self.rows.append({"name": name, "value": float(value), "limit": float(limit), "ok": ok})

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def as_dict(self) -> Dict[str, list]:
        return {r["name"]: [r["value"], r["limit"]] for r in self.rows}

    def lines(self) -> List[str]:
        return [
            "compared %-22s value %.6g  limit %.6g  %s"
            % (r["name"], r["value"], r["limit"], "ok" if r["ok"] else "FAIL")
            for r in self.rows
        ]
