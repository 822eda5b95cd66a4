"""The language-model family's scopes, from the traced run's
``.xplane.pb``: the time of each named part of the decoder *inside the
round executable*, per round.

``models/decoder.py`` wraps its parts in ``jax.named_scope``
(``lm.embed``, ``blk.attn.window``, ``blk.attn.full``, ``moe.route``,
``moe.experts``, ``moe.combine``, ``lm.head_loss``). ``_scopes.py``
resolves an ``XLA Ops`` event to its ``op_name`` (event statistic,
metadata record, embedded HLO, whichever first names a scope) and its
list of scope names is closed; this file widens that list for one more
pass over the same trace and keeps only the events that ran inside
``jit_round_fn``: the same scopes inside ``jit_eval_all`` belong to the
evaluation, which ``eval_device_ms`` already reads. A scope's time is
the union of its events' intervals, averaged over chips. On a program
without these scopes (or with no trace) every reader returns None.
"""

from __future__ import annotations

import bisect
import collections
import json
import sys
from typing import Dict, List, Optional, Tuple

import _scopes
from reduce_trace import DEVICE_PLANE, is_control_flow, short_name, union_length

LM_SCOPES = (
    "lm.embed", "blk.attn.window", "blk.attn.full", "moe.route", "moe.experts", "moe.combine",
    "lm.head_loss",
)
ROUND = "jit_round_fn"
# local training as this family has to count it: the expert layer's lane
# loop starts JAX's name stack anew, so its operations carry their moe.*
# scope and not the ``fed.local_train`` round them
TRAINING = frozenset(LM_SCOPES + ("fed.local_train",))
LOCAL_TRAIN = "lm.local_train"
RAGGED = "ragged-dot"


def reduce_lm_scopes(data, raw: Optional[bytes] = None) -> Dict[str, float]:
    """``{scope: seconds inside jit_round_fn}``, plus ``"lm.local_train"``
    (everything under ``fed.local_train`` or an LM scope) for the share
    the scopes cover; ``{}`` where the trace names none."""
    firsts: Dict[Tuple[str, str], Tuple[str, List[str]]] = {}
    devices = []
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        modules = sorted(
            (int(ev.start_ns), int(ev.start_ns + ev.duration_ns), ev.name)
            for ev in (lines["XLA Modules"].events if "XLA Modules" in lines else ()))
        starts = [mod[0] for mod in modules]
        keyed = []
        for ev in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
            s = int(ev.start_ns)
            i = bisect.bisect_right(starts, s) - 1
            module = modules[i][2] if i >= 0 and s < modules[i][1] else ""
            if not module.startswith(ROUND):
                continue
            key = (module, ev.name)
            if key not in firsts:
                firsts[key] = (plane.name, [v for k, v in ev.stats
                                            if k in _scopes.OP_NAME_STATS and isinstance(v, str)])
            keyed.append((key, s, s + int(ev.duration_ns)))
        if keyed:
            devices.append(keyed)
    if not devices:
        return {}
    closed = _scopes.SCOPES
    _scopes.SCOPES = closed + LM_SCOPES  # the resolver's list, for this pass
    try:
        scopes_of = _scopes._Resolver(raw).resolve(firsts)
    finally:
        _scopes.SCOPES = closed
    # the chip compiler's expansion of the ragged product
    # (``ragged-dot-none.<n>``) keeps no op metadata at all (my chip run,
    # PR 28: 36 such operations, 0.72 s a round, under no scope): they
    # are the expert layer's grouped product, found by their name
    for key in scopes_of:
        if short_name(key[1]).startswith(RAGGED) and not scopes_of[key]:
            scopes_of[key] = ("moe.experts",)
    totals: Dict[str, float] = collections.defaultdict(float)
    unnamed: Dict[str, float] = collections.defaultdict(float)
    for keyed in devices:
        by_scope: Dict[str, list] = collections.defaultdict(list)
        for key, s, e in keyed:
            for scope in scopes_of[key]:
                by_scope[scope].append((s, e))
            if set(scopes_of[key]) & TRAINING:
                by_scope[LOCAL_TRAIN].append((s, e))
            if not is_control_flow(key[1]) and not set(scopes_of[key]) & set(LM_SCOPES):
                unnamed[short_name(key[1])] += (e - s) / 1e9 / len(devices)
        for scope, ivals in by_scope.items():
            totals[scope] += union_length(ivals) / 1e9 / len(devices)
    if not any(scope in totals for scope in LM_SCOPES):
        return {}
    # for whoever looks for the time no scope names: the largest leaf
    # operations of the round executable outside every LM scope
    print("lm_unnamed " + json.dumps(
        [[k, round(v, 4)] for k, v in sorted(unnamed.items(), key=lambda kv: -kv[1])[:12]]),
        file=sys.stderr, flush=True)
    return dict(totals)


def summary(ctx) -> Dict[str, float]:
    """This run's reduction, made on first use."""
    if "_lm_scopes" not in ctx:
        path = _scopes.find_trace(ctx["cell"].name)
        out: Dict[str, float] = {}
        if path is not None:
            from jax.profiler import ProfileData

            with open(path, "rb") as f:
                raw = f.read()
            out = reduce_lm_scopes(ProfileData.from_serialized_xspace(raw), raw)
        ctx["_lm_scopes"] = out
        if out:
            named = sum(out.get(s, 0.0) for s in LM_SCOPES)
            print("lm_scopes " + json.dumps({
                **{k: round(v, 6) for k, v in sorted(out.items())},
                "named_over_local_train": round(named / max(out.get(LOCAL_TRAIN, 0.0), 1e-12), 4),
            }), file=sys.stderr, flush=True)
    return ctx["_lm_scopes"]


def ms_per_round(ctx, *scopes: str):
    """Device milliseconds of the named scopes together, per run of the
    round executable; None where the trace names none of them."""
    found = summary(ctx)
    runs = sum(m["count"] for name, m in ctx["trace"]["modules"].items() if ROUND in name)
    seconds = sum(found.get(s, 0.0) for s in scopes)
    if seconds <= 0 or not runs:
        return None
    return 1e3 * seconds / runs


def flash_fwd_roofline(ctx, kernel: str, window_of):
    """A flash forward kernel's share of its roofline over the traced
    window: the least time the chip could take for every sequence it
    pushed through the kernel (the larger of operations over peak FLOP/s
    and bytes over peak bytes/s, both from shapes) over the kernel's
    whole device time. A training step runs it twice a layer (the
    forward pass and the rematerialised one), an evaluation once, padded
    sequence slots included: the kernel computes them."""
    k = ctx["trace"]["kernels"].get(kernel)
    model, win = ctx["cell"].config.get("model", {}), ctx["window"]
    if not k or not k["count"] or k["total_s"] <= 0 or "layer_types" not in model:
        return None
    window = window_of(model)
    layers = sum(1 for kind in model["layer_types"]
                 if (kind == "sliding_attention") == (window is not None))
    passes = 2.0 if ctx["cell"].config.get("program_args", {}).get("remat") else 1.0
    sequences = passes * win.get("slot_samples", 0.0) + win.get("eval_slot_samples", 0.0)
    need = ctx["flops"].flash_fwd_sequence(model, window)
    peaks = ctx["peaks"]
    least = max(need["flops"] / peaks["bf16_flops_per_s"], need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * sequences * layers / (k["total_s"])
