"""``_lane_scopes.py``'s pass over the traced run with the scopes of
``models/decoder.py``'s gated attention and sandwich norms:
``blk.attn.gate`` (the gate's projection, sigmoid and product, inside
``blk.attn.window`` / ``blk.attn.full``), ``blk.post_norm`` (both
sandwich norms, inside the sublayer's own scope where it has one) and
``moe.shared`` (the shared expert). ``_lm_scopes``' lists are closed, so
they are widened for the pass, as ``_ssm_scopes.py`` widens them for
its own, and put back.

One pass over the operations inside ``jit_round_fn``, made on first use
and kept in ``ctx`` (the trace read once, through ``_ssm_scopes``); it
prints a ``gated_scopes`` line whose ``named_over_local_train`` counts
every scope but the two nested ones, and ``_lm_scopes``' ``lm_unnamed``
line. On a program without these scopes (or with no trace) every reader
returns None.
"""

from __future__ import annotations

import json
import sys

import _lane_scopes
import _lm_scopes
import _ssm_scopes

NESTED = ("blk.attn.gate", "blk.post_norm")  # inside other scopes: read, not summed with them
NAMED = _lane_scopes.LANE_SCOPES + ("moe.shared",)


def summary(ctx):
    if "_gated_scopes" not in ctx:
        trace, out = _ssm_scopes._trace(ctx), {}
        if trace is not None:
            closed = _lm_scopes.LM_SCOPES, _lm_scopes.TRAINING
            _lm_scopes.LM_SCOPES = NAMED + NESTED
            _lm_scopes.TRAINING = frozenset(NAMED + NESTED + ("fed.local_train",))
            try:
                out = _lm_scopes.reduce_lm_scopes(*trace)
            finally:
                _lm_scopes.LM_SCOPES, _lm_scopes.TRAINING = closed
        ctx["_gated_scopes"] = out
        if out:
            named = sum(out.get(s, 0.0) for s in NAMED)
            print("gated_scopes " + json.dumps({
                **{k: round(v, 6) for k, v in sorted(out.items())},
                "named_over_local_train": round(
                    named / max(out.get(_lm_scopes.LOCAL_TRAIN, 0.0), 1e-12), 4),
            }), file=sys.stderr, flush=True)
    return ctx["_gated_scopes"]


def ms_per_round(ctx, *scopes: str):
    """Device milliseconds of the named scopes together, per run of the
    round executable; None where the trace names none of them."""
    return _lm_scopes.ms_per_round({**ctx, "_lm_scopes": summary(ctx)}, *scopes)
