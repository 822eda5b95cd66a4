"""Helpers the one-file readers share. A reader is
``read(ctx) -> float | None``; ``ctx`` holds ``cell``, ``window`` (the
driver's counts), ``trace`` (``reduce_trace``'s summary), ``facts``
(spans and counters the driver took), ``peaks``, ``device``, ``flops``
(the configuration's module) and ``setup_s``. A reader that finds
nothing to read returns None and the metric is left out of the line."""

from __future__ import annotations


def module_ms_per_call(ctx, needle: str, per: float = 1.0):
    """Mean device milliseconds of the executable whose name holds
    ``needle``, per call (or per ``per`` units a call covers)."""
    hits = [m for name, m in ctx["trace"]["modules"].items() if needle in name]
    count = sum(m["count"] for m in hits)
    if not count:
        return None
    return 1e3 * sum(m["total_s"] for m in hits) / count / per


def mfu_pct(ctx):
    win, peak = ctx["window"], ctx["peaks"]["bf16_flops_per_s"]
    flops = ctx["flops"].window_flops(ctx["cell"], win)
    if flops <= 0 or win["wall_s"] <= 0:
        return None
    return 100.0 * flops / win["wall_s"] / (ctx["device"]["count"] * peak)


def device_idle_pct(ctx):
    dev = ctx["device"]
    if dev.get("busy_s", 0) <= 0 or dev.get("window_s", 0) <= 0:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])


def peak_hbm_pct(ctx):
    peak = ctx["device"].get("memory_peak_bytes", 0)
    if peak <= 0:
        return None
    return 100.0 * peak / ctx["peaks"]["hbm_bytes"]
