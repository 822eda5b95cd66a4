"""Every ``jax.named_scope`` the package opens, in one registry.

A scope is a component of the ``op_name`` in an HLO instruction's
metadata: it costs nothing at run time, survives whatever number the
compiler gives an instruction, and is how a device trace's time is told
apart (``benchmark/layer_metrics``; ``docs/observability.md``). Each
name is of one of two kinds:

- a *container* holds a whole phase of an executable, or a construct
  round layers, and names no time of its own: ``fed.local_train`` (the
  cohort's local training), ``fwd_bwd`` (a step's forward and backward),
  ``fed.eval`` (the evaluation) and ``moe.loop`` (the expert layer's
  lane and chunk loops round ``moe.route`` / ``experts`` / ``combine``).
  What runs inside a container and inside no layer is *unnamed*: a
  reader reports it by its innermost container (``moe.loop``'s is the
  loops' own slices, carries and stacked outputs);
- a *layer* names the time of the operations under it: the round's
  gather and aggregation, the update, a step's casts and loss, the
  decoder's parts (``lm.*``, ``blk.*``, ``moe.*``) and the evaluation's
  own passes (``eval.*``). Layers may nest (``blk.ssm.scan`` inside
  ``blk.ssm``, ``blk.attn.gate`` inside an attention scope,
  ``blk.post_norm`` inside its sublayer's); a reader takes the union of
  their intervals.

``tests/test_scopes.py`` fails when a ``named_scope`` literal
in ``fedml_tpu/`` is missing here; the benchmark's coverage reader
(``benchmark/layer_metrics/_coverage.py``) takes its lists from here.
"""

from __future__ import annotations

from typing import Tuple

CONTAINER, LAYER = "container", "layer"

SCOPES: Tuple[Tuple[str, str], ...] = (
    # simulation/fedavg_api.build_round_fn / build_eval_all
    ("fed.gather", LAYER),
    ("fed.local_train", CONTAINER),
    ("fed.aggregate", LAYER),
    ("fed.eval", CONTAINER),
    # core/local_trainer: a step, and an evaluation's batch
    ("fwd_bwd", CONTAINER),
    ("opt", LAYER),
    ("step.cast", LAYER),
    ("eval.cast", LAYER),
    ("eval.loss", LAYER),
    # models/decoder.py (and the loss half of lm.head_loss in core/losses.py)
    ("lm.embed", LAYER),
    ("lm.head_loss", LAYER),
    ("blk.attn.window", LAYER),
    ("blk.attn.full", LAYER),
    ("blk.attn.gate", LAYER),
    ("blk.conv", LAYER),
    ("blk.mlp.dense", LAYER),
    ("blk.ffn_norm", LAYER),
    ("blk.post_norm", LAYER),
    ("blk.ssm", LAYER),
    ("blk.ssm.scan", LAYER),
    ("moe.route", LAYER),
    ("moe.experts", LAYER),
    ("moe.combine", LAYER),
    ("moe.shared", LAYER),
    ("moe.loop", CONTAINER),
    ("moe.chunk", LAYER),
    ("moe.grad_sum", LAYER),
    ("moe.out", LAYER),
)

NAMES: Tuple[str, ...] = tuple(name for name, _ in SCOPES)
CONTAINERS: Tuple[str, ...] = tuple(name for name, kind in SCOPES if kind == CONTAINER)
LAYERS: Tuple[str, ...] = tuple(name for name, kind in SCOPES if kind == LAYER)
