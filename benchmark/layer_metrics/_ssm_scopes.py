"""``_lane_scopes.py``'s pass over the traced run with the scopes of
``models/decoder.py``'s state-space and shared-expert parts:
``blk.ssm`` (a ``mamba`` sublayer: norm, ``in_proj``, convolution,
scan, gated norm, ``out_proj``), ``blk.ssm.scan`` *inside* it
(everything ``ops/ssd.py`` does) and ``moe.shared`` (the shared
expert). ``_lm_scopes``' lists are closed, so they are widened for a
pass, as ``_lane_scopes.py`` widens them for its own, and put back.

Two passes, each made on first use and kept in ``ctx``: one over the
operations inside ``jit_round_fn`` (``summary``: what the ``*_device_ms``
readers divide by the rounds run; it prints an ``ssm_scopes`` line whose
``named_over_local_train`` counts every scope but the nested one, and
``_lm_scopes``' ``lm_unnamed`` line), one over those inside
``jit_eval_all`` (``eval_summary``: the evaluations' forward passes,
which ``ssm_scan_roofline`` counts beside training). On a program
without these scopes (or with no trace) every reader returns None.
"""

from __future__ import annotations

import json
import sys

import _lane_scopes
import _lm_scopes
import _scopes

NESTED = ("blk.ssm.scan",)  # inside blk.ssm: read, not summed with it
NAMED = _lane_scopes.LANE_SCOPES + ("blk.ssm", "moe.shared")
EVAL = "jit_eval_all"


def _trace(ctx):
    """The traced run's ``(ProfileData, bytes)``, read once for both
    passes; None where there is no trace."""
    if "_ssm_trace" not in ctx:
        path, ctx["_ssm_trace"] = _scopes.find_trace(ctx["cell"].name), None
        if path is not None:
            from jax.profiler import ProfileData

            with open(path, "rb") as f:
                raw = f.read()
            ctx["_ssm_trace"] = ProfileData.from_serialized_xspace(raw), raw
    return ctx["_ssm_trace"]


def _reduce(ctx, executable: str):
    """``_lm_scopes.reduce_lm_scopes`` over the operations inside the
    executables whose name starts with ``executable``, with the lists
    widened; ``{}`` where there is no trace or it names no scope."""
    trace = _trace(ctx)
    if trace is None:
        return {}
    closed = _lm_scopes.LM_SCOPES, _lm_scopes.TRAINING, _lm_scopes.ROUND
    _lm_scopes.LM_SCOPES = NAMED + NESTED
    _lm_scopes.TRAINING = frozenset(NAMED + NESTED + ("fed.local_train",))
    _lm_scopes.ROUND = executable
    try:
        return _lm_scopes.reduce_lm_scopes(*trace)
    finally:
        _lm_scopes.LM_SCOPES, _lm_scopes.TRAINING, _lm_scopes.ROUND = closed


def summary(ctx):
    if "_ssm_scopes" not in ctx:
        out = ctx["_ssm_scopes"] = _reduce(ctx, _lm_scopes.ROUND)
        if out:
            named = sum(out.get(s, 0.0) for s in NAMED)
            print("ssm_scopes " + json.dumps({
                **{k: round(v, 6) for k, v in sorted(out.items())},
                "named_over_local_train": round(
                    named / max(out.get(_lm_scopes.LOCAL_TRAIN, 0.0), 1e-12), 4),
            }), file=sys.stderr, flush=True)
    return ctx["_ssm_scopes"]


def eval_summary(ctx):
    if "_ssm_scopes_eval" not in ctx:
        ctx["_ssm_scopes_eval"] = _reduce(ctx, EVAL)
    return ctx["_ssm_scopes_eval"]


def ms_per_round(ctx, *scopes: str):
    """Device milliseconds of the named scopes together, per run of the
    round executable; None where the trace names none of them."""
    return _lm_scopes.ms_per_round({**ctx, "_lm_scopes": summary(ctx)}, *scopes)


def seconds_in_window(ctx, scope: str):
    """Device seconds of one scope over the whole traced window, inside
    the round executable and the evaluation's; None where neither names
    it."""
    total = summary(ctx).get(scope, 0.0) + eval_summary(ctx).get(scope, 0.0)
    return total if total > 0 else None
