"""Persistent XLA compilation cache, placed from outside.

Compiling is most of a cold start on the chip (the ResNet cohort round
alone is tens of seconds), and a chip machine may be thrown away after
every command — so the cache directory is something the operator
places, not something the program invents:

1. ``JAX_COMPILATION_CACHE_DIR`` set: JAX itself reads it at import;
   no code here (or anywhere) calls
   ``jax.config.update("jax_compilation_cache_dir", ...)``, and a
   ``compile_cache_dir`` knob naming another directory is ignored with
   a warning.
2. otherwise the ``compile_cache_dir`` knob, when given;
3. otherwise, on a TPU, the fixed ``<checkout>/.jax_compile_cache``
   (git-ignored). The path is part of the cache key's locality — a
   directory that moves never hits — so it is never a temp, pid or
   timestamp path;
4. otherwise (CPU, nothing asked for) the cache stays off, so a test
   run leaves the checkout clean.

``maybe_enable_compile_cache(args)`` is idempotent and process-wide
(``jax.config`` is process-global): the first caller wins, a later knob
naming a different directory logs one warning and keeps the first.
``fedml_tpu.init()`` calls it before any data synthesis compiles, and
every engine init calls it again for callers that skip ``init()``. The
min-compile-time / min-entry-size floors drop to zero so the small
per-bucket round executables are cached too.

Hit/miss counts: a ``jax.monitoring`` listener counts
``/jax/compilation_cache/cache_hits`` / ``cache_misses`` into
``stats()`` and, when telemetry is on, into
``compile_cache_hits_total`` / ``compile_cache_misses_total`` with the
directory gauged as ``compile_cache_entries``. Beside it a second
listener writes one ``compile`` instant into the flight recorder for
each executable the backend builds, cache or no cache.
"""

from __future__ import annotations

import logging
import os
from typing import Dict, Optional

_ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_compile_cache",
)

_EVENT_HITS = "/jax/compilation_cache/cache_hits"
_EVENT_MISSES = "/jax/compilation_cache/cache_misses"
_EVENT_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

# process-scoped: the directory the cache was enabled with (None =
# never enabled). jax.config is process-global, so this module is too.
_enabled_dir: Optional[str] = None
_listener_installed = False
_warned_conflict = False
_counts = {"hits": 0, "misses": 0}


def _on_event(event: str, **kwargs) -> None:
    """jax.monitoring listener: count cache hit/miss events here and in
    the telemetry registry (host-side counter bumps only)."""
    if event not in (_EVENT_HITS, _EVENT_MISSES):
        return
    hit = event == _EVENT_HITS
    _counts["hits" if hit else "misses"] += 1
    from .telemetry import Telemetry

    tel = Telemetry.get_instance()
    if not tel.enabled:
        return
    if hit:
        tel.inc("compile_cache_hits_total")
    else:
        tel.inc("compile_cache_misses_total")
        # a miss just wrote an entry — keep the directory gauge live
        # (one listdir per compile, which already cost far more)
        tel.set_gauge("compile_cache_entries", cache_entries())


def _on_duration(event: str, duration: float, **kwargs) -> None:
    """jax.monitoring listener: one ``compile`` instant in the flight
    recorder for each executable the backend builds (compiled or read
    from the persistent cache), its seconds as an arg -- so a round
    that stalled on one says so in the run's own timeline."""
    if event != _EVENT_BACKEND_COMPILE:
        return
    from .telemetry import Telemetry

    Telemetry.get_instance().recorder.instant(
        "compile", cat="compile", seconds=round(float(duration), 6)
    )


def install_listeners() -> None:
    """Register the two listeners above, once a process
    (``jax.monitoring`` has no way to take one back)."""
    global _listener_installed
    if _listener_installed:
        return
    from jax import monitoring

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    _listener_installed = True


def cache_entries(directory: Optional[str] = None) -> int:
    """Number of cache files currently in the (given or enabled)
    cache directory; 0 when disabled/absent."""
    d = directory or _enabled_dir
    if not d or not os.path.isdir(d):
        return 0
    return sum(1 for n in os.listdir(d) if not n.startswith("."))


def enabled_dir() -> Optional[str]:
    return _enabled_dir


def stats() -> Dict[str, object]:
    """Where the cache is and what this process got from it."""
    return {"dir": _enabled_dir, "entries": cache_entries(), **_counts}


def resolve_dir(args) -> Optional[str]:
    """The directory rules 1-4 of the module docstring pick for
    ``args`` in this process, or None for "cache off"."""
    env = os.environ.get(_ENV_DIR)
    if env:
        return os.path.abspath(env)
    knob = getattr(args, "compile_cache_dir", None)
    if knob:
        return os.path.abspath(str(knob))
    import jax

    return CHECKOUT_CACHE_DIR if jax.default_backend() == "tpu" else None


def maybe_enable_compile_cache(args) -> bool:
    """Enable the persistent compilation cache where ``resolve_dir``
    says. Returns True when the cache is active (now or from an
    earlier call)."""
    global _enabled_dir, _warned_conflict
    install_listeners()
    if _enabled_dir is None:
        d = resolve_dir(args)
        if d is None:
            return False
        os.makedirs(d, exist_ok=True)
        import jax

        if not os.environ.get(_ENV_DIR):
            # jax 0.9 builds its cache object lazily at the first
            # compile that finds a directory configured, so setting it
            # after earlier compiles needs no reset of jax's internals
            jax.config.update("jax_compilation_cache_dir", d)
        # cache EVERYTHING: the round/fold/serving executables compile
        # in milliseconds on CPU but in minutes on a TPU pod — the
        # default 1s floor would skip exactly the census we warm-start
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        # op metadata is part of the key: the round executable's scopes
        # (fedavg_api.build_round_fn) are read from device traces, and
        # with JAX's default an entry written by a tree that had other
        # scopes, or none, is served with *its* metadata ("executables
        # loaded from the cache may have stale metadata, which may show
        # up in, e.g., profiles" -- the flag's own help)
        jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
        # ... of which each op's name stack and its own source line,
        # not the ten frames of traceback JAX adds by default: with
        # those the key would also name whoever called train(), and
        # every entry script would compile the round executable for
        # itself. (jax_include_full_tracebacks_in_locations=False would
        # do the same and more: it drops the name stack of everything
        # inside a loop body, scopes included -- seen on the chip.)
        jax.config.update("jax_traceback_in_locations_limit", 1)
        _enabled_dir = d
        from .telemetry import Telemetry

        tel = Telemetry.get_instance()
        if tel.enabled:
            tel.set_gauge("compile_cache_entries", cache_entries(d))
        logging.info("persistent compilation cache enabled at %s", d)
    knob = getattr(args, "compile_cache_dir", None)
    if (
        knob
        and os.path.abspath(str(knob)) != _enabled_dir
        and not _warned_conflict
    ):
        _warned_conflict = True
        logging.warning(
            "compile_cache_dir=%s ignored: the process-wide XLA "
            "compilation cache is already rooted at %s (%s; jax.config "
            "is process-global, one directory per process)",
            knob, _enabled_dir,
            f"{_ENV_DIR} is set" if os.environ.get(_ENV_DIR)
            else "first caller wins",
        )
    return True
