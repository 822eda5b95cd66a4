"""Observability: event spans, metrics reporting, run logging.

Parity with the reference's MLOps subsystem (SURVEY.md §5) behind
interfaces with no platform dependency:

- ``ProfilerEvent`` ~ ``MLOpsProfilerEvent``
  (core/mlops/mlops_profiler_event.py:11-100): STARTED/ENDED spans
  around ``train`` / ``comm`` / ``server.wait`` / ``aggregate``; here
  spans also record device wall time and are queryable in-process
  (the reference fires JSON into MQTT and forgets).
- ``MetricsReporter`` ~ ``MLOpsMetrics`` (mlops_metrics.py:15-120):
  round/train/test metrics to pluggable sinks (logging, JSONL file,
  user callback) instead of fixed MQTT topics.
- ``RunLogger`` ~ ``MLOpsRuntimeLog`` (mlops_runtime_log.py:12-221):
  per-run log files with the chunked-upload seam kept as an interface
  (the reference uploads 100-line chunks to open.fedml.ai).

Beyond the reference (SURVEY.md §5: "No torch-profiler integration"):
spans also open a ``jax.profiler.TraceAnnotation`` so they appear as
named regions in an XLA device trace, and ``device_trace(args)``
captures a full trace (tensorboard/perfetto ``.xplane.pb``) for any
run that sets ``args.profile_dir`` — the knob works identically on CPU
and TPU.
"""

from __future__ import annotations

import gc
import json
import logging
import os
import time
from collections import defaultdict
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, Optional

EVENT_TYPE_STARTED = 0  # mlops_profiler_event.py:12
EVENT_TYPE_ENDED = 1  # mlops_profiler_event.py:13


class ProfilerEvent:
    """Span recorder. ``log_event_started(name)`` /
    ``log_event_ended(name)`` mirror the reference API; ``span(name)``
    is the one primitive the round loops are timed with: it opens a
    ``jax.profiler.TraceAnnotation`` (the device trace's own clock),
    mirrors B/E into the flight recorder and, at its end, observes the
    ``span_seconds{name}`` histogram (docs/observability.md lists the
    names)."""

    _instance: Optional["ProfilerEvent"] = None

    def __init__(self, args=None) -> None:
        self.args = args
        self.run_id = getattr(args, "run_id", "0") if args else "0"
        self._open: Dict[str, float] = {}
        self.totals: Dict[str, float] = defaultdict(float)
        self.counts: Dict[str, int] = defaultdict(int)
        # set by Telemetry.attach_profiler: spans are mirrored into the
        # flight recorder's trace.json timeline and observed into the
        # registry (core/telemetry.py)
        self.recorder = None
        self.telemetry = None
        self._steal_ticks: Optional[int] = None
        self._gc_done: List[Any] = []  # (t0, t1, generation, collected)

    @classmethod
    def get_instance(cls, args=None) -> "ProfilerEvent":
        if cls._instance is None:
            cls._instance = cls(args)
        elif args is not None and cls._instance.args is None:
            # a later caller finally supplied args: adopt them instead
            # of silently ignoring them (the old singleton bug)
            cls._instance.args = args
            cls._instance.run_id = getattr(args, "run_id", "0")
        return cls._instance

    @classmethod
    def reset(cls) -> None:
        """Drop the singleton so state cannot leak across tests."""
        cls._instance = None

    def log_event_started(
        self, event_name: str, value: Any = None, **trace_args: Any
    ) -> None:
        self._open[event_name] = time.perf_counter()
        if self.recorder is not None:
            self.recorder.begin(event_name, cat="profiler", **trace_args)

    def log_event_ended(
        self, event_name: str, value: Any = None, **trace_args: Any
    ) -> None:
        t0 = self._open.pop(event_name, None)
        if t0 is None:
            logging.warning("span %r ended without start", event_name)
            return
        dt = time.perf_counter() - t0
        self._flush_gc()  # collections that ran inside this span, first
        if self.recorder is not None:
            self.recorder.end(event_name, cat="profiler", **trace_args)
        self._tally(event_name, dt)

    def span(self, name: str, **trace_args: Any):
        """Context-manager sugar the reference lacks. ``trace_args``
        land on the mirrored flight-recorder B event and on the trace
        annotation (round / rank tags the critical-path analyzer
        reads); what the body puts into the span's ``end_args`` lands
        on its E event."""
        return _Span(self, name, **trace_args)

    @contextmanager
    def iteration_span(self, name: str, **trace_args: Any):
        """The span of one iteration of a round or epoch loop
        (``round``, ``epoch``). Its E event carries ``steal_ticks``:
        what the hypervisor took from this machine's CPUs
        (``/proc/stat``) since the previous iteration ended, or since
        ``watch_stalls`` began."""
        from .sys_stats import cpu_steal_ticks

        with self.span(name, **trace_args) as sp:
            try:
                yield sp
            finally:
                ticks = cpu_steal_ticks()
                if ticks is not None:
                    if self._steal_ticks is not None:
                        sp.end_args["steal_ticks"] = ticks - self._steal_ticks
                    self._steal_ticks = ticks

    @contextmanager
    def watch_stalls(self):
        """While inside, every garbage collection is a ``gc`` span
        (``generation`` and ``collected`` as args) and the steal count
        starts here: with JAX's compile events
        (``core/compile_cache.py``) the three things that stall a round
        loop from outside it.

        A collection can begin at any allocation, also inside the
        flight recorder's or the registry's lock, so the callback takes
        no lock: it opens the trace annotation, keeps two clock
        readings, and the next span to end (or this block's end) writes
        the pair into the recorder at those times."""
        from jax.profiler import TraceAnnotation

        from .sys_stats import cpu_steal_ticks

        running: List[Any] = []

        def on_gc(phase: str, info: Dict[str, int]) -> None:
            if phase == "start":
                ann = TraceAnnotation("gc", generation=info["generation"])
                ann.__enter__()
                running[:] = [time.perf_counter(), ann]
            elif running:
                t0, ann = running
                del running[:]
                ann.__exit__(None, None, None)
                self._gc_done.append(
                    (t0, time.perf_counter(), info["generation"], info["collected"])
                )

        self._steal_ticks = cpu_steal_ticks()
        gc.callbacks.append(on_gc)
        try:
            yield
        finally:
            gc.callbacks.remove(on_gc)
            self._flush_gc()

    def _flush_gc(self) -> None:
        while self._gc_done:
            t0, t1, generation, collected = self._gc_done.pop(0)
            if self.recorder is not None:
                self.recorder.complete(
                    "gc", t0, t1, cat="profiler",
                    generation=generation, collected=collected,
                )
            self._tally("gc", t1 - t0)

    def _tally(self, name: str, dt: float) -> None:
        self.totals[name] += dt
        self.counts[name] += 1
        if self.telemetry is not None:
            self.telemetry.observe("span_seconds", dt, name=name)

    def summary(self) -> Dict[str, Dict[str, float]]:
        return {
            k: {"total_s": self.totals[k], "count": self.counts[k]}
            for k in self.totals
        }


class _Span:
    def __init__(self, ev: ProfilerEvent, name: str, **trace_args: Any) -> None:
        self.ev, self.name = ev, name
        self.trace_args = trace_args
        self.end_args: Dict[str, Any] = {}
        self._annotation = None

    def __enter__(self):
        self.ev.log_event_started(self.name, **self.trace_args)
        # named region in any active XLA device trace (no-op otherwise)
        import jax.profiler

        self._annotation = jax.profiler.TraceAnnotation(self.name, **self.trace_args)
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
            self._annotation = None
        self.ev.log_event_ended(self.name, **self.end_args)
        return False


class device_trace:
    """Capture an XLA device trace for a whole run when
    ``args.profile_dir`` is set; inert otherwise. View with
    ``tensorboard --logdir <profile_dir>`` or perfetto."""

    def __init__(self, args=None) -> None:
        self.logdir = getattr(args, "profile_dir", None) if args else None
        self._active = False

    def __enter__(self):
        if self.logdir:
            import jax.profiler

            os.makedirs(self.logdir, exist_ok=True)
            jax.profiler.start_trace(self.logdir)
            self._active = True
            logging.info("device trace capturing to %s", self.logdir)
        return self

    def __exit__(self, *exc):
        if self._active:
            import jax.profiler

            jax.profiler.stop_trace()
            self._active = False
        return False


Sink = Callable[[Dict[str, Any]], None]


class DeferredMetrics:
    """Device-resident metric ring for the round pipeline.

    The round-pipeline executor (``core/round_pipeline.py``) keeps its
    hot loop free of host syncs: per-round metric scalars stay on
    device and are ``push``ed here; ``flush`` materializes every pending
    record in ONE device fetch. ``host_syncs`` counts those fetches —
    the instrumentation the zero-sync-between-flushes test asserts on.

    Contract: ``push`` never touches device values; ``flush(upto)``
    fetches (and removes) all records with ``round_idx <= upto`` (None
    = everything, the drain case) and returns ``[(round_idx, host_tree),
    ...]`` in push order, where ``host_tree`` holds numpy scalars.
    """

    def __init__(self) -> None:
        self._pending: List[Any] = []  # [(round_idx, device_tree)]
        self.host_syncs = 0
        self.flushes = 0

    def __len__(self) -> int:
        return len(self._pending)

    def push(self, round_idx: int, device_tree: Any) -> None:
        self._pending.append((round_idx, device_tree))

    def flush(self, upto: Optional[int] = None):
        ready: List[Any] = []
        keep: List[Any] = []
        for rec in self._pending:  # one pass, push order preserved
            (ready if upto is None or rec[0] <= upto else keep).append(rec)
        if not ready:
            return []
        self._pending = keep
        import jax

        host = jax.device_get([t for _, t in ready])  # ONE fetch for all
        self.host_syncs += 1
        self.flushes += 1
        return list(zip([r for r, _ in ready], host))


class MetricsReporter:
    """Round/train/test metrics to pluggable sinks."""

    def __init__(self, args=None, keep_history: bool = True) -> None:
        self.sinks: List[Sink] = []
        self.keep_history = keep_history
        self.history: List[Dict[str, Any]] = []
        path = getattr(args, "metrics_jsonl_path", None) if args else None
        if path:
            self.add_jsonl_sink(path)
        if args is None or getattr(args, "log_metrics", True):
            self.sinks.append(lambda rec: logging.info("metrics: %s", rec))

    def add_sink(self, sink: Sink) -> None:
        self.sinks.append(sink)

    def add_jsonl_sink(self, path: str) -> None:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)

        def write(rec: Dict[str, Any]) -> None:
            with open(path, "a") as f:
                f.write(json.dumps(rec) + "\n")

        self.sinks.append(write)

    def report(self, record: Dict[str, Any]) -> None:
        rec = {"ts": time.time(), **record}
        if self.keep_history:
            self.history.append(rec)
        for s in self.sinks:
            try:
                s(rec)
            except Exception:
                logging.exception("metrics sink failed")

    # reference-API aliases (mlops_metrics.py)
    def report_server_training_metric(self, metric: Dict[str, Any]) -> None:
        self.report({"kind": "server_train", **metric})

    def report_client_training_metric(self, metric: Dict[str, Any]) -> None:
        self.report({"kind": "client_train", **metric})


class RunLogger:
    """Per-run file logging with an upload seam."""

    _instance: Optional["RunLogger"] = None
    CHUNK_LINES = 100  # mlops_runtime_log.py:13

    def __init__(self, args=None) -> None:
        self.args = args
        self.uploader: Optional[Callable[[List[str]], None]] = None
        self._pending: List[str] = []

    @classmethod
    def get_instance(cls, args=None) -> "RunLogger":
        if cls._instance is None:
            cls._instance = cls(args)
        elif args is not None and cls._instance.args is None:
            # adopt late-supplied args instead of silently ignoring them
            cls._instance.args = args
        return cls._instance

    @classmethod
    def reset(cls) -> None:
        """Drop the singleton so state cannot leak across tests."""
        cls._instance = None

    def init_logs(self, log_dir: Optional[str] = None) -> None:
        run_id = getattr(self.args, "run_id", "0") if self.args else "0"
        rank = getattr(self.args, "rank", 0) if self.args else 0
        handlers: List[logging.Handler] = [logging.StreamHandler()]
        if log_dir:
            os.makedirs(log_dir, exist_ok=True)
            path = os.path.join(log_dir, f"run_{run_id}_rank_{rank}.log")
            handlers.append(logging.FileHandler(path))
        logging.basicConfig(
            level=logging.INFO,
            format="[%(asctime)s %(levelname)s rank" + str(rank) + "] %(message)s",
            handlers=handlers,
            force=True,
        )

    def set_uploader(self, fn: Callable[[List[str]], None]) -> None:
        """Chunked-upload seam (mlops_runtime_log.py:41-47)."""
        self.uploader = fn

    def upload_line(self, line: str) -> None:
        if self.uploader is None:
            return
        self._pending.append(line)
        if len(self._pending) >= self.CHUNK_LINES:
            self.flush()

    def flush(self) -> None:
        if self.uploader and self._pending:
            self.uploader(list(self._pending))
            self._pending.clear()
