"""``_lm_scopes.py``'s pass over the traced run with two more scopes of
``models/decoder.py``: ``blk.conv`` (a ``conv`` layer's operator) and
``blk.mlp.dense`` (a leading layer's dense MLP), and with
``local_trainer``'s ``opt`` (the update: a pass over every weight a
step, which a step of few tokens does not hide) counted among the named.
``_lm_scopes.LM_SCOPES`` is closed, so it is widened for this pass
alone, as that file widens ``_scopes.SCOPES`` for its own; the pass
prints a second ``lm_scopes`` line whose ``named_over_local_train``
counts the three, and a second ``lm_unnamed`` line: the largest
operations of local training that no scope names (the weights' bf16
cast, the float32 sums of the expert stacks' gradients). Kept in ``ctx``
under a key of its own. On a program without these scopes (or with no
trace) every reader returns None.
"""

from __future__ import annotations

import _lm_scopes

LANE_SCOPES = _lm_scopes.LM_SCOPES + ("blk.conv", "blk.mlp.dense", "opt")


def summary(ctx):
    if "_lane_scopes" not in ctx:
        closed = _lm_scopes.LM_SCOPES, _lm_scopes.TRAINING
        _lm_scopes.LM_SCOPES = LANE_SCOPES
        _lm_scopes.TRAINING = frozenset(LANE_SCOPES + ("fed.local_train",))
        try:
            ctx["_lane_scopes"] = _lm_scopes.summary({"cell": ctx["cell"]})
        finally:
            _lm_scopes.LM_SCOPES, _lm_scopes.TRAINING = closed
    return ctx["_lane_scopes"]


def ms_per_round(ctx, *scopes: str):
    """Device milliseconds of the named scopes together, per run of the
    round executable; None where the trace names none of them."""
    return _lm_scopes.ms_per_round({**ctx, "_lm_scopes": summary(ctx)}, *scopes)
