"""L0 configuration layer: YAML -> flat ``Arguments``.

Parity with the reference's ``python/fedml/arguments.py``:

- ``add_args()`` exposes exactly the reference's CLI surface: ``--cf`` for
  the YAML path and ``--rank`` (arguments.py:32-49).
- ``Arguments`` flattens the sectioned YAML (``common_args`` /
  ``data_args`` / ``model_args`` / ``train_args`` / ``validation_args`` /
  ``device_args`` / ``comm_args`` / ``tracking_args``) into flat attributes
  (arguments.py:138-141).
- When no config is given, a shipped default config is used
  (arguments.py:56-104 behavior), see ``fedml_tpu/config/``.

Improvements over the reference (which has "no typed schema, no
validation", SURVEY.md §5): defaults are declared in one table, values are
type-coerced, and unknown training/backend combinations fail fast.
"""

from __future__ import annotations

import argparse
import os
from pathlib import Path
from typing import Any, Dict, Optional

import yaml

from . import constants

# Defaults applied when neither the YAML nor the caller provides a value.
# This doubles as the (otherwise implicit) schema of well-known knobs.
_DEFAULTS: Dict[str, Any] = {
    "training_type": constants.FEDML_TRAINING_PLATFORM_SIMULATION,
    "backend": constants.FEDML_SIMULATION_TYPE_SP,
    "scenario": constants.FEDML_CROSS_SILO_SCENARIO_HORIZONTAL,
    "random_seed": 0,
    # data
    "dataset": "synthetic",
    "data_cache_dir": "./data_cache",
    "partition_method": constants.PARTITION_HETERO,
    "partition_alpha": 0.5,
    # padded-packing long-tail policy: shared num_batches is clamped to
    # waste_cap x median client size; samples beyond it are truncated
    # (pack_clients logs what was dropped). float("inf") disables.
    "packing_waste_cap": 4.0,
    # resized-image ingestion (imagenet / gld* folders and CSVs): H=W
    # decode size; the synthetic stand-ins follow the same knob
    "image_size": 64,
    # model
    "model": "lr",
    # training
    "federated_optimizer": constants.FED_OPTIMIZER_FEDAVG,
    "client_id_list": None,
    "client_num_in_total": 10,
    "client_num_per_round": 10,
    "comm_round": 10,
    "epochs": 1,
    "batch_size": 32,
    "client_optimizer": "sgd",
    "learning_rate": 0.03,
    "momentum": 0.0,
    "weight_decay": 0.0,
    "server_optimizer": "sgd",
    "server_lr": 1.0,
    "server_momentum": 0.0,
    # fedprox / fednova
    "fedprox_mu": 0.0,
    # simulation engine mode: "vectorized" (vmap the cohort — the TPU
    # path, driven by the async round pipeline) or "sequential"
    # (python loop per client — the reference's shape, debug/parity)
    "sim_mode": "vectorized",
    # server aggregation mode (core/aggregation.py StreamingAccumulator
    # + cross_silo managers): "stream" folds each upload into O(model)
    # running accumulators the moment it lands (bit-identical results
    # to "buffered"; falls back to the buffered path LOUDLY when the
    # aggregation needs the full cohort at once, e.g. defense_type or a
    # custom ServerAggregator); "buffered" keeps the reference's
    # buffer-then-aggregate shape; "async" is the FedBuff-style mode:
    # no round barrier, staleness-weighted folds, a publish every
    # async_publish_every folds
    "agg_mode": "stream",
    # quorum round close (streaming modes): once this fraction of the
    # round's live cohort has folded, arm a round_grace_s timer; when
    # it fires the round closes over the partial cohort (weights
    # renormalize) and late uploads are discarded by round tag. Ranks
    # the failure detector declares dead leave the quorum denominator.
    # 0 disables (wait for everyone, the reference shape)
    "round_quorum_frac": 0.0,
    # how long past quorum the server keeps waiting for stragglers
    "round_grace_s": 0.0,
    # async staleness weighting: an upload trained against a model s
    # publishes old folds with weight sample_num * staleness_decay^s
    "staleness_decay": 0.5,
    # async hard staleness cap: updates staler than this are discarded
    # (counted agg_stale_discarded_total), never folded
    "staleness_max": 10,
    # async publish cadence: finalize + publish the global model (and
    # checkpoint it when checkpoint_dir is set, feeding the serving
    # plane's hot-swap watcher) every K folds
    "async_publish_every": 4,
    # straggler handling (cross-silo; beyond the reference): aggregate
    # whoever reported within this many seconds of the round broadcast,
    # reweighted over the subset. 0 = wait for everyone (reference).
    "aggregation_deadline_s": 0.0,
    # on a deadline with ZERO uploads the server rebroadcasts the round
    # (the downlink may have been lost) at most this many times, then
    # shuts the federation down instead of extending forever
    "aggregation_deadline_max_extensions": 3,
    # uplink compression (cross-silo; beyond the reference): clients
    # ship encoded update deltas instead of full fp32 params.
    # "none" | "int8" (4x, lossless-ish) | "topk" (ratio-controlled
    # sparsification with error feedback, core/compression.py)
    "compression": "none",
    "compression_topk_ratio": 0.01,
    # elastic membership (cross-silo; beyond the reference): start once
    # client_num_per_round clients are online, accept mid-run joins,
    # survive OFFLINE leaves. False = fixed membership (reference).
    "elastic_membership": False,
    # validation
    "frequency_of_the_test": 5,
    # device
    "using_gpu": True,
    "device_type": "tpu",
    "gpu_mapping_file": None,
    # comm
    "grpc_ipconfig_path": None,
    "grpc_port_base": 8890,
    # tracking
    "enable_tracking": False,
    "run_id": "0",
    # fault injection (core/comm/faults.py — beyond the reference):
    # mapping of {drop_prob, duplicate_prob, delay_s, delay_prob, seed,
    # msg_types, max_faults}; None disables
    "fault_injection": None,
    # deterministic chaos plane (core/chaos.py): an ordered list of
    # one-shot fault steps {at: {event, occurrence, round?, rank?,
    # msg_type?, name?}, fault: kind-or-mapping} driving exact-message
    # comm faults, WAL/checkpoint IO faults (torn write, failed fsync,
    # ENOSPC, latency, torn publish), process kills at named barriers
    # and clock skew. None disables
    "chaos_schedule": None,
    # seed for any randomness a schedule step asks for (latency
    # jitter); an identical (chaos_schedule, chaos_seed) pair
    # reproduces the identical fault trace
    "chaos_seed": 0,
    # IO-only fault steps (same step shape, events wal_create /
    # wal_append / ckpt_publish only) — convenience for faulting the
    # durable-write seam without a full schedule. None disables
    "io_faults": None,
    # reliable delivery (core/comm/reliable.py): wrap every comm
    # endpoint in an ack/retransmit channel with receive-side dedup —
    # effectively exactly-once delivery over a lossy network. Enable on
    # ALL processes of a world together.
    "reliable_comm": False,
    # reliable channel: how many retransmits before a send is given up
    # (the product of the backoff series is the channel's send timeout)
    "comm_retry_max": 5,
    # first-retry backoff; doubles per attempt with up to +50% jitter
    "comm_retry_base_s": 0.2,
    # per-attempt deadline of one gRPC unary send (the seed's fixed
    # timeout=300); the transport retries transient RPC errors a small
    # fixed number of times (deliberately NOT comm_retry_max — the
    # reliable channel's retransmits call back into this send, and
    # sharing the knob would multiply the budgets), then raises a typed
    # CommSendError instead of whatever grpc surfaces
    "grpc_send_timeout_s": 300.0,
    # client liveness beats (core/comm/heartbeat.py): emit
    # MSG_TYPE_C2S_HEARTBEAT this often; the beats double as the
    # reconnect probe after a server restart. 0 disables
    "heartbeat_interval_s": 0.0,
    # server failure detector: declare a client dead after this long
    # with NO traffic (beats, uploads, status) and fold it into the
    # OFFLINE/deadline-cohort paths so a kill -9'd client can never
    # stall a round. Use 3-5x heartbeat_interval_s. 0 disables
    "heartbeat_timeout_s": 0.0,
    # robustness (reference: fedavg_robust example config). defense_type:
    # "norm_diff_clipping" | "weak_dp" | "median" | None. Clipping and
    # weak_dp are per-upload and ride the streaming/async fold
    # (core/aggregation.py clipped term executables; weak-DP noise
    # drawn at finalize from a run-seed+round key); median needs the
    # full cohort and keeps the buffered path. Unknown strings are
    # rejected loudly — never silently aggregated undefended.
    "defense_type": None,
    # norm-diff clip radius: each upload's delta against the broadcast
    # global is scaled to at most this L2 norm
    "norm_bound": 5.0,
    # weak-DP Gaussian noise stddev added to the finalized aggregate
    "stddev": 0.158,
    # on-arrival anomaly screen (core/defense.py AnomalyScreen): uploads
    # are scored (norm excess + cosine to the running aggregate) into a
    # per-rank reputation EWMA; a rank whose reputation crosses this
    # threshold is QUARANTINED — uploads rejected before folding, rank
    # excluded from cohorts until probation expires. 0 disables. Note
    # screening decisions are arrival-order dependent, so the
    # stream==buffered bit-identity guarantee applies with 0 only
    "defense_anomaly_threshold": 0.0,
    # quarantine probation length, in round closes (sync) or publishes
    # (async); release restores a fresh reputation
    "defense_quarantine_rounds": 3,
    # poisoned-world synthesis (data/poison.py, loader wiring): attack
    # type for the attacker clients — "label_flip" | "targeted_flip" |
    # "backdoor_pattern" | "edge_case", or a list paired 1:1 with
    # poisoned_client_idxs for mixed-attack worlds. None disables
    "poison_type": None,
    # explicit attacker client indexes (wins over the fraction)
    "poisoned_client_idxs": None,
    # else: this fraction of clients is drawn as attackers (seeded)
    "poisoned_client_fraction": 0.0,
    # label the attacks steer toward (backdoor/edge_case/targeted_flip)
    "target_label": 0,
    # fraction of each attacker's samples that are poisoned
    "poison_sample_fraction": 1.0,
    # planet-scale population plane (fedml_tpu/scale/): register this
    # many clients as columnar state (~17 bytes each) and draw cohorts
    # from the registry with O(cohort) memory per round, datasets
    # materialized on demand. 0 = off (eager federation, the default).
    # Simulation-only; requires a classification task and the stock
    # FedAvg/FedProx server step
    "client_registry_size": 0,
    # registry-mode cohort drawn per round (0 = client_num_per_round)
    "cohort_size": 0,
    # two-tier aggregation tree (fedml_tpu/scale/tree.py): this many
    # edge aggregators each fold their subtree through the streaming
    # accumulator and the root folds the edge partials — bit-identical
    # to flat aggregation. Applies to the registry-backed simulator AND
    # the cross-silo streaming server (agg_mode=stream). 0/1 = flat
    "edge_num": 0,
    # hierarchical server plane (cross_silo/hierarchical — docs/
    # hierarchical.md): "inproc" keeps the edge tier inside the server
    # process (the PR 9 tree); "ranks" promotes the edge_num edges to
    # REAL ranks over the comm seam — clients upload to their assigned
    # edge, each edge streams-folds + screens locally and ships one
    # merged limb-set per round close, the root merges bit-identically
    # to flat. Requires training_type=cross_silo + agg_mode=stream
    "edge_plane": "inproc",
    # gRPC port stride between per-edge client fabrics (each fabric
    # binds grpc_port_base + edge_rank * stride + rank); must exceed
    # the client count. LOCAL fabrics are name-strided and ignore it
    "hier_port_stride": 64,
    # back the registry columns with .npy memmaps under this directory
    # instead of host RAM (None = in-RAM numpy)
    "registry_dir": None,
    # A/B bit-identity harness (tests/test_planet_scale.py): partition terms
    # per edge exactly as the tree would, but fold them into ONE flat
    # accumulator — the baseline the tree identity is asserted against
    "edge_flat_fold": False,
    # precision: the 3-decimal equivalence oracles need f32 matmuls
    "matmul_precision": "highest",
    # mixed precision (core/local_trainer.py): "bfloat16" runs the
    # forward/backward matmuls in the MXU's native format with f32
    # master weights, optimizer state, and loss reductions
    "dtype": "float32",
    # async round pipeline (core/round_pipeline.py): how many federation
    # rounds may be in flight at once. 1 = synchronous (identical
    # metrics, flushed every eval round); K>1 defers metric fetches so
    # the hot loop has zero host syncs between flushes
    "pipeline_depth": 1,
    # compile-cache bucket policy for cohort sizes: "pow2" pads the
    # sampled cohort up to the next power of two (zero-weight,
    # fully-masked padding) so cohort-size changes hit the jit cache;
    # "exact" disables padding (auto-selected for weight-unaware
    # aggregation, e.g. defense_type=median or a custom
    # server_aggregator)
    "pipeline_bucket": "pow2",
    # mesh axes -> sizes. Scenario-specific vocabulary: the distributed
    # platform (distributed.py) takes {dp/tp/ep} | {sp} | {pp}; the
    # MESH simulation backend (simulation/simulator.py) takes the fed
    # production vocabulary {data, fsdp} (cohort over data, params
    # sharded at rest over fsdp — docs/multichip.md) or the legacy
    # {clients, data}. None = scenario default (all devices, one axis)
    "mesh_shape": None,
    # capture an XLA device trace (tensorboard/perfetto) for the run
    "profile_dir": None,
    # flight-recorder telemetry (core/telemetry.py): process-wide
    # counters/gauges/histograms + Chrome-trace event ring. False
    # disables every instrument (comm counting, pipeline events,
    # watchdog); the hot loop is host-side either way
    "telemetry": True,
    # write run artifacts here: trace.json (perfetto-loadable merged
    # timeline), metrics.prom (Prometheus text exposition),
    # telemetry.jsonl (registry snapshots) and stall debug bundles.
    # None = keep everything in-process only
    "telemetry_dir": None,
    # stall watchdog: if NO progress heartbeat (pipeline round, comm
    # send/receive, cross-silo round) advances for this many seconds,
    # dump a debug bundle (open spans, pending deferred metrics, last-N
    # trace events, host+device sys_stats) to telemetry_dir. 0 disables
    "stall_timeout_s": 0.0,
    # flight-recorder ring capacity (events). Overflow evicts oldest,
    # counted in telemetry_trace_dropped_total and the exported trace's
    # meta — a run that outgrows the ring is visible, not silent
    "trace_ring_size": 65536,
    # devtime wall-clock ring capacity (core/devtime.py): per-dispatch
    # {executable, bucket, seconds} entries kept for the perf plane's
    # fallback join when histogram snapshots are unavailable
    "devtime_ring_size": 4096,
    # on-demand device profiling (core/tracing.py RoundProfiler): round
    # indices (list or "1,5,9" string) to capture a programmatic
    # jax.profiler trace for, into telemetry_dir/profile/round_NNNN.
    # No-op with one logged warning on backends without capture support
    "profile_rounds": None,
    # pull-based exposition: serve Telemetry.prometheus_text() at
    # http://<metrics_host>:<port>/metrics for the run's lifetime.
    # 0 (default) = off
    "metrics_port": 0,
    # bind address for the /metrics server. Loopback by default: the
    # endpoint is unauthenticated, so exposing it on the network is an
    # explicit choice ("0.0.0.0"), never the default
    "metrics_host": "127.0.0.1",
    # per-round latency SLO (cross-silo server): a round whose wall
    # time (broadcast -> aggregate done) exceeds this many seconds
    # counts into slo_violations_total. 0 disables
    "round_deadline_s": 0.0,
    # serving plane (fedml_tpu/serving — `fedml_tpu.cli serve`):
    # bounded request queue; a full queue sheds new requests
    # (serving_shed_total{reason=queue_full}) instead of growing
    "serve_queue_size": 256,
    # micro-batch cap: the batcher drains up to this many queued
    # requests into one forward pass (pow2-bucketed below the cap)
    "serve_max_batch": 64,
    # linger time while assembling a micro-batch once the first
    # request is in hand — the latency/occupancy tradeoff knob
    "serve_batch_wait_ms": 2.0,
    # default per-request deadline; requests still queued past it are
    # shed (serving_shed_total{reason=deadline}). 0 disables
    "serve_deadline_ms": 100.0,
    # serving batch-shape bucket policy: "pow2" (compile once per
    # bucket, the training cohort cache's rule) or "exact"
    "serve_bucket": "pow2",
    # checkpoint publish/watch poll interval for weight hot-swaps
    "serve_watch_interval_s": 1.0,
    # serving fleet: number of endpoints behind the fleet frontend
    # (1 = the classic single-endpoint plane, no fleet layer)
    "serve_fleet_size": 1,
    # serve on a named (data, fsdp) mesh: {"data": D, "fsdp": F} makes
    # every endpoint a MeshModelEndpoint (params at their at-rest
    # SpecLayout shardings, batches sharded along data). None = serve
    # single-device
    "serve_mesh": None,
    # fleet routing policy: "least_loaded" (argmin queue depth per
    # request) or "static" (the boustrophedon deal cycled —
    # core/scheduler.assign_by_load)
    "serve_route_policy": "least_loaded",
    # fleet SLO shed signal: when the p99 of serving_request_latency_s
    # exceeds this, new requests shed at the fleet door
    # (serving_fleet_shed_total{reason=slo}). 0 disables
    "serve_route_slo_ms": 0.0,
    # on an immediately-shed submission (queue full / stopped engine)
    # retry this many more candidates before giving up
    "serve_route_failover": 1,
    # sequence-parallel strategy: "ring" or "ulysses"
    "sp_strategy": "ring",
    # ring attention: chunk each hop's K/V shard so the per-chip score
    # panel is [Tq, sp_ring_block] instead of [Tq, T/sp] — the memory
    # knob for very long resident shards (0 = whole shard per hop)
    "sp_ring_block": 0,
    # rematerialize transformer blocks (jax.checkpoint): trade FLOPs
    # for HBM — recompute block activations in the backward pass
    "remat": False,
    "pp_microbatches": 0,  # 0 = auto (2 x pipeline stages)
    # weight of the Switch MoE load-balancing aux loss in the
    # distributed trainer's objective (0 disables)
    "moe_aux_weight": 0.01,
    # gradient accumulation in the distributed trainer: chunk each
    # batch into N grad passes before one update (HBM lever); exact
    # (count-weighted) vs the unchunked masked-mean gradient
    "grad_accum_steps": 1,
    # learning-rate schedule (core/optimizers.py): "constant" or
    # "cosine". Two index bases, exactly one may be set with cosine:
    # lr_total_steps (optimizer steps — the distributed trainer) or
    # lr_total_rounds (federation rounds — FL scenarios, where the
    # client optimizer re-inits per round and the natural semantics is
    # decay across rounds)
    "lr_schedule": "constant",
    "lr_total_steps": 0,
    "warmup_steps": 0,
    "lr_total_rounds": 0,
    "warmup_rounds": 0,
    # auto-fetch supported dataset archives into data_cache_dir when no
    # local copy exists (reference data/MNIST/data_loader.py:17-29
    # behavior; off by default so offline runs never stall on egress)
    "download": False,
    # persistent XLA compilation cache (core/compile_cache.py): root
    # the content-addressed jit cache here so a warm re-launch (10k
    # cohort world, mesh sweep, serving restart) skips every compile
    # whose (HLO, flags, platform) key it has seen — hits/misses are
    # counted in compile_cache_hits_total/_misses_total. One directory
    # per process (process-global jax.config). The environment's
    # JAX_COMPILATION_CACHE_DIR wins over this knob; None means the
    # fixed <checkout>/.jax_compile_cache on a TPU and no cache on CPU
    "compile_cache_dir": None,
    # crash recovery / serving feed (core/checkpoint.py): directory for
    # orbax round checkpoints + the round WAL. None disables both —
    # a crashed server then restarts the federation from round 0
    "checkpoint_dir": None,
    # save a checkpoint every N completed rounds. None keeps each
    # scenario's historical cadence (simulation: every 10 rounds;
    # cross-silo/distributed: every round; async ALWAYS checkpoints
    # every publish regardless — see fedml_server_manager)
    "checkpoint_freq": None,
    # elastic membership: highest client rank an unknown ONLINE may
    # register as — one misconfigured hello must not bloat the server
    # with ghost ranks
    "max_clients": 4096,
    # elastic preemption signal (parallel/elastic.py): None/"none"
    # disables; "round:K" fires a scripted maintenance drill at round
    # K; "file:PATH" fires when PATH exists (external supervisor);
    # "metadata" polls the GCE metadata maintenance-event endpoint
    # (real TPU VMs); "chaos" rides a scheduled preempt/device.loss
    # fault on the elastic.check event. Requires checkpoint_dir: a
    # notice with nowhere durable to land is a config error, not a
    # runtime surprise
    "preempt_signal": None,
    # elastic resume floor: refuse to resume on fewer surviving
    # devices than this (below it the operator wants a page, not a
    # crawl) — enforced by parallel/elastic.surviving_mesh
    "elastic_min_devices": 1,
    # ---- scenario / model-geometry knobs (schema burn-down) ---------
    # Every knob below was read via getattr(...) with an inline
    # fallback but had no schema entry (the lint suite's registry
    # rule); defaults here MATCH those read-site fallbacks exactly, so
    # unset configs behave identically. seq_len and the real-data
    # subsample sizes keep dynamic per-site fallbacks and stay
    # baselined.

    "shuffle": True,  # reshuffle each client's examples every local epoch
    "output_dim": 10,  # class/label count for the synthetic-style loaders
    "synthetic_feature_dim": 2000,  # synthetic-fedprox feature width
    "synthetic_sigma": 1.0,  # synthetic feature noise scale
    "synthetic_alpha": 1.0,  # fedprox-synthetic u_k spread
    "synthetic_beta": 1.0,  # fedprox-synthetic v_k spread
    "vocab_size": 0,  # LM vocabulary (0 = the model family's default)
    "num_layers": 2,  # transformer depth
    "num_heads": 4,  # attention heads
    "embed_dim": 128,  # transformer model width
    "max_len": 512,  # positional-embedding capacity
    "hidden_dim": 64,  # MLP hidden width
    # "full" (dense) | "flash" (ops/flash_attention.py; seq_len must be
    # a multiple of 128 on every platform)
    "attention_impl": "full",
    "seg_width": 32,  # segsum attention panel width
    "moe_every": 2,  # every Nth transformer block is a Switch MoE layer
    "num_experts": 8,  # Switch MoE expert count
    "capacity_factor": 1.25,  # MoE per-expert token capacity slack
    # model: moe_decoder (models/decoder.py): RMSNorm, grouped-KV rotary
    # attention (window and full layers) or a gated short convolution as
    # a layer's operator, a dense gated-SiLU MLP on the leading layers
    # and routed gated-SiLU experts after them, a tied or untied head
    "hidden_size": 256,  # moe_decoder model width
    "num_kv_heads": 2,  # moe_decoder KV heads (num_heads a multiple)
    "head_dim": 64,  # moe_decoder head width (not tied to hidden_size / num_heads)
    # moe_decoder layer pattern, one entry per layer: "sliding_attention"
    # | "full_attention" | "conv" (None = num_layers full layers); with
    # sublayers also "mamba" | "moe" and no "conv"
    "layer_types": None,
    # moe_decoder: every layer_types entry is ONE sublayer, x + mixer(norm(x)):
    # attention, the routed experts ("moe") or a Mamba-2 mixer ("mamba"),
    # instead of an operator followed by a feed-forward
    "sublayers": False,
    "qk_norm": True,  # moe_decoder: RMS-normalise q and k per head before the rotation
    # moe_decoder: gate attention's output by sigmoid(gate_proj(normed input)) before
    # o_proj (not in the sublayer form)
    "output_gate": False,
    # moe_decoder: RMS-normalise the attention's and the feed-forward's outputs
    # before the residual takes them (the sandwich norm; refused in the sublayer
    # form and beside a conv layer)
    "post_norms": False,
    "embedding_multiplier": 1.0,  # moe_decoder: multiply the embedded rows (muP's sqrt(hidden))
    # moe_decoder "mamba" sublayers (ops/ssd.py): heads and their width
    # (the inner width is their product), B / C groups, state size, the
    # causal depthwise convolution's taps, the scan's chunk
    "ssm_num_heads": 4,
    "ssm_head_dim": 64,
    "ssm_groups": 1,
    "ssm_state_size": 128,
    "ssm_conv_kernel": 4,
    "ssm_chunk_size": 128,
    "conv_L_cache": 3,  # moe_decoder: taps of a conv layer's causal depthwise filter
    "num_dense_layers": 0,  # moe_decoder: leading layers whose feed-forward is a dense MLP
    "intermediate_size": 0,  # moe_decoder: that dense MLP's width
    "sliding_window": 1024,  # moe_decoder: keys a sliding layer sees, itself included
    # moe_decoder rotary parameters per layer type, as a published
    # config.json has them: {layer type: {rope_type: default | yarn,
    # rope_theta, factor, original_max_position_embeddings, beta_fast,
    # beta_slow, attention_factor}} (None = rope_type default at
    # rope_theta 10000 on both layer types; an attention type the
    # given table leaves out is not rotated, {} rotates none)
    "rope_parameters": None,
    "experts_per_token": 2,  # moe_decoder: experts a token is routed to (top-k)
    "expert_dim": 128,  # moe_decoder: width of one expert's gated MLP
    "norm_topk_prob": True,  # moe_decoder: renormalise the top-k routing weights
    "norm_topk_eps": 0.0,  # moe_decoder: added to the top-k weights' sum before dividing by it
    "router_scoring": "softmax",  # moe_decoder: "softmax" | "sigmoid" over all experts, float32
    # moe_decoder: a float32 leaf added to the scores for the top-k choice
    # only (the weights stay the unbiased scores: its gradient is zero)
    "use_expert_bias": False,
    # moe_decoder: an expert is "gated_silu" (down(silu(gate x) * up x)) |
    # "relu2" (down(relu(up x) ** 2), no gate)
    "expert_activation": "gated_silu",
    # moe_decoder: width of a shared expert of the same form, on every
    # token beside the routed ones (0 = none)
    "shared_expert_dim": 0,
    "routed_scaling_factor": 1.0,  # moe_decoder: multiplies the (renormalised) top-k weights
    # moe_decoder: the chunk the held experts' sorted choices are worked
    # through in, in even loads (a step whose share passes it runs another)
    "expert_chunk_loads": 1.25,
    "tie_word_embeddings": False,  # moe_decoder: the head is the embedding's rows
    "rms_norm_eps": 1e-6,  # moe_decoder RMSNorm epsilon
    # moe_decoder: the chips that share each expert layer, and which of
    # them this is: the layer holds num_experts / expert_parallel
    # experts from expert_rank's first, routes over all num_experts and
    # computes its own experts' part (parallel/expert.py experts_held)
    "expert_parallel": 1,
    "expert_rank": 0,
    "nas_width": 16,  # FedNAS stem channels
    "nas_cells": 2,  # FedNAS cells per client model
    "nas_steps": 2,  # FedNAS nodes per cell
    "arch_learning_rate": 0.0003,  # FedNAS architecture-weight LR
    "gan_latent_dim": 64,  # FedGAN generator latent size
    "gan_lr_g": 0.0002,  # FedGAN generator LR
    "gan_lr_d": 0.0002,  # FedGAN discriminator LR
    "splitnn_stages": (1, 1, 1),  # SplitNN (client, server, head) depths
    "vfl_parties": 2,  # vertical-FL feature-holding parties
    "vfl_rep_dim": 32,  # vertical-FL per-party representation width
    "gkt_server_stages": (2, 2, 2),  # FedGKT server tower depths
    "gkt_alpha": 1.0,  # FedGKT distillation loss weight
    "gkt_temperature": 3.0,  # FedGKT softmax temperature
    "gkt_server_epochs": 1,  # FedGKT server epochs per round
    "group_num": 2,  # hierarchical-FL group count
    "group_method": "random",  # hierarchical-FL grouping rule
    "group_comm_round": 1,  # hierarchical-FL intra-group rounds
    "topology_neighbor_num": 2,  # decentralized ring/random neighbors
    "topology_beta": 0.0,  # PushSum topology asymmetry
    "ta_groups": 4,  # TurboAggregate circular groups
    "ta_quant_scale": 65536.0,  # TurboAggregate additive-share scale
    "sfedavg_alpha": 0.5,  # S-FedAvg reputation weight (goodness)
    "sfedavg_beta": 0.5,  # S-FedAvg reputation weight (history)
    "sampling_filter": "exp",  # S-FedAvg score->probability filter
    "score_method": "acc",  # S-FedAvg client scoring signal
    "sv_tol": 0.005,  # Shapley truncation tolerance
    # Shapley permutation cap; None = auto (client_num_per_round ** 2,
    # the reference's cohort**2 distance-sample cap)
    "sv_max_perms": None,
    "valid_batches": 4,  # validation batches for defense scoring
    "hs_L": 0.0,  # HS-FedAvg FFT band (0 = derive from the input)
    "hs_momentum": 0.1,  # HS-FedAvg spectral-mask momentum
    "server_beta1": 0.9,  # FedOpt adam/yogi first-moment decay
    "server_beta2": 0.999,  # FedOpt adam/yogi second-moment decay
    "broker_host": "127.0.0.1",  # MQTT broker bind address
    "broker_port": 0,  # MQTT broker port (0 = per-run local broker)
    "trpc_ipconfig_path": None,  # TRPC fabric rank->ip CSV
    "trpc_port_base": None,  # TRPC first port (rank k = base+k)
    "payload_store_dir": None,  # spill oversized comm payloads here
    "log_metrics": True,  # mirror server metrics into the run log
    "metrics_jsonl_path": None,  # also append metrics as JSONL here
    # cross-device control plane (cross_device/server.py)
    "cross_device_backend": constants.COMM_BACKEND_MQTT,
    # cross-device Beehive check-in plane (cross_device/gateway.py)
    "crossdevice_cohort": 0,  # devices sampled per round (0 = client_num_per_round)
    "crossdevice_fold_target_frac": 0.6,  # fold-count fraction that closes a round
    "crossdevice_report_window_s": 30.0,  # report window after the check-in phase
    "crossdevice_secure_agg": True,  # pairwise-mask uploads (cancel in the fold)
    "crossdevice_quant_scale": 65536.0,  # field quantization scale for deltas
    "crossdevice_mask_threshold": 2,  # Shamir threshold for dropout recovery
    "crossdevice_duty_hours": 14,  # diurnal on-window length per device
    "crossdevice_verify_pubkey": True,  # check revealed secrets against pubkeys
    "silo_backend": "LOCAL",  # hierarchical cross-silo in-silo fabric
    "silo_grpc_port_base": 9890,  # in-silo gRPC first port
    "silo_grpc_ipconfig_path": None,  # in-silo rank->ip CSV
    "silo_device_count": 0,  # devices per silo (0 = all local devices)
}

_SECTIONS = (
    "common_args",
    "data_args",
    "model_args",
    "train_args",
    "validation_args",
    "device_args",
    "comm_args",
    "tracking_args",
    "defense_args",
    "attack_args",
)


class Arguments:
    """Flat attribute bag over a sectioned YAML config.

    Reference parity: ``Arguments`` at ``python/fedml/arguments.py:52-141``
    — ``load_yaml_config`` then ``set_attr_from_config`` flattening every
    section's keys onto ``self``.
    """

    def __init__(
        self,
        cmd_args: Optional[argparse.Namespace] = None,
        training_type: Optional[str] = None,
        comm_backend: Optional[str] = None,
    ) -> None:
        self._raw: Dict[str, Any] = {}
        if cmd_args is not None:
            for k, v in vars(cmd_args).items():
                setattr(self, k, v)
        config_path = getattr(self, "yaml_config_file", None) or None
        if config_path:
            self.load_yaml_config(config_path)
        for key, val in _DEFAULTS.items():
            if not hasattr(self, key):
                setattr(self, key, val)
        if training_type is not None:
            self.training_type = training_type
        if comm_backend is not None:
            self.backend = comm_backend
        self._validate()

    # -- YAML ----------------------------------------------------------
    def load_yaml_config(self, path: str) -> None:
        with open(path, "r") as f:
            cfg = yaml.safe_load(f) or {}
        self._raw = cfg
        self.set_attr_from_config(cfg)

    def set_attr_from_config(self, configuration: Dict[str, Any]) -> None:
        """Flatten sections (arguments.py:138-141)."""
        for section, content in configuration.items():
            if isinstance(content, dict) and (
                section in _SECTIONS or section.endswith("_args")
            ):
                for key, val in content.items():
                    setattr(self, key, val)
            else:
                setattr(self, section, content)

    # -- validation ----------------------------------------------------
    def _validate(self) -> None:
        t = self.training_type
        valid = {
            constants.FEDML_TRAINING_PLATFORM_SIMULATION,
            constants.FEDML_TRAINING_PLATFORM_CROSS_SILO,
            constants.FEDML_TRAINING_PLATFORM_CROSS_DEVICE,
            constants.FEDML_TRAINING_PLATFORM_DISTRIBUTED,
        }
        if t not in valid:
            raise ValueError(f"unknown training_type {t!r}; expected one of {sorted(valid)}")
        from .core.local_trainer import compute_dtype_from_args

        compute_dtype_from_args(self)  # single choke point; raises on bad dtype
        if self.client_num_per_round > self.client_num_in_total:
            self.client_num_per_round = self.client_num_in_total
        if (
            t == constants.FEDML_TRAINING_PLATFORM_CROSS_SILO
            and self.backend
            in (constants.COMM_BACKEND_SP, constants.FEDML_SIMULATION_TYPE_SP)
        ):
            # the simulation default backend makes no sense cross-silo;
            # LOCAL runs single-host worlds, GRPC is the networked path
            self.backend = constants.COMM_BACKEND_LOCAL
        for int_key in (
            "client_num_in_total",
            "client_num_per_round",
            "comm_round",
            "epochs",
            "batch_size",
            "random_seed",
            "pipeline_depth",
            "serve_queue_size",
            "serve_max_batch",
            "serve_fleet_size",
            "serve_route_failover",
            "comm_retry_max",
        ):
            setattr(self, int_key, int(getattr(self, int_key)))
        if getattr(self, "pipeline_depth", 1) < 1:
            raise ValueError(
                f"pipeline_depth={self.pipeline_depth}: must be >= 1 "
                "(1 = synchronous round loop)"
            )
        if getattr(self, "pipeline_bucket", "pow2") not in ("pow2", "exact"):
            raise ValueError(
                f"pipeline_bucket {self.pipeline_bucket!r}: pick 'pow2' or 'exact'"
            )
        if getattr(self, "sim_mode", "vectorized") not in (
            "vectorized", "sequential",
        ):
            raise ValueError(
                f"sim_mode {self.sim_mode!r}: pick 'vectorized' or 'sequential'"
            )
        for float_key in (
            "learning_rate",
            "server_lr",
            "partition_alpha",
            "fedprox_mu",
            "compression_topk_ratio",
            "stall_timeout_s",
            "serve_batch_wait_ms",
            "serve_deadline_ms",
            "serve_watch_interval_s",
            "serve_route_slo_ms",
            "comm_retry_base_s",
            "grpc_send_timeout_s",
            "heartbeat_interval_s",
            "heartbeat_timeout_s",
        ):
            setattr(self, float_key, float(getattr(self, float_key)))
        if self.comm_retry_max < 0:
            raise ValueError(
                f"comm_retry_max={self.comm_retry_max}: must be >= 0 "
                "(0 = no retransmits/retries)"
            )
        for nonneg_key in (
            "comm_retry_base_s", "heartbeat_interval_s", "heartbeat_timeout_s",
        ):
            if getattr(self, nonneg_key) < 0:
                raise ValueError(
                    f"{nonneg_key}={getattr(self, nonneg_key)}: must be >= 0"
                )
        if self.grpc_send_timeout_s <= 0:
            raise ValueError(
                f"grpc_send_timeout_s={self.grpc_send_timeout_s}: must be > 0"
            )
        if getattr(self, "agg_mode", "stream") not in (
            "stream", "buffered", "async",
        ):
            raise ValueError(
                f"agg_mode {self.agg_mode!r}: pick 'stream' (aggregate-on-"
                "arrival), 'buffered' (reference shape) or 'async' (FedBuff)"
            )
        for float_key in ("round_quorum_frac", "round_grace_s", "staleness_decay"):
            setattr(self, float_key, float(getattr(self, float_key)))
        if not 0.0 <= self.round_quorum_frac <= 1.0:
            raise ValueError(
                f"round_quorum_frac={self.round_quorum_frac}: must be in "
                "[0, 1] (0 disables the quorum close)"
            )
        if self.round_grace_s < 0:
            raise ValueError(
                f"round_grace_s={self.round_grace_s}: must be >= 0"
            )
        if not 0.0 < self.staleness_decay <= 1.0:
            raise ValueError(
                f"staleness_decay={self.staleness_decay}: must be in (0, 1] "
                "(1 = no staleness discount)"
            )
        for int_key in ("staleness_max", "async_publish_every"):
            setattr(self, int_key, int(getattr(self, int_key)))
        if self.staleness_max < 0:
            raise ValueError(
                f"staleness_max={self.staleness_max}: must be >= 0 "
                "(0 = only fresh updates fold)"
            )
        if self.async_publish_every < 1:
            raise ValueError(
                f"async_publish_every={self.async_publish_every}: must be >= 1"
            )
        if (
            getattr(self, "agg_mode", "stream") == "async"
            and float(getattr(self, "aggregation_deadline_s", 0) or 0) > 0
        ):
            raise ValueError(
                "agg_mode=async has no round barrier; "
                "aggregation_deadline_s does not apply — unset one of them"
            )
        # -- chaos plane knobs (docs/robustness.md chaos schedule DSL) --
        from .core.chaos import validate_schedule

        validate_schedule(getattr(self, "chaos_schedule", None), "chaos_schedule")
        io_steps = validate_schedule(getattr(self, "io_faults", None), "io_faults")
        bad_io = [
            s for s in io_steps
            if s["at"]["event"] not in ("wal_create", "wal_append", "ckpt_publish")
        ]
        if bad_io:
            raise ValueError(
                f"io_faults only takes IO events (wal_create / wal_append / "
                f"ckpt_publish); got {sorted(s['at']['event'] for s in bad_io)}"
                " — use chaos_schedule for comm/barrier steps"
            )
        raw = getattr(self, "chaos_seed", 0)
        try:
            self.chaos_seed = int(raw or 0)
        except (TypeError, ValueError):
            raise ValueError(
                f"chaos_seed={raw!r}: must be an integer"
            ) from None
        # -- elastic preemption knobs (docs/robustness.md device loss) --
        from .parallel.elastic import make_signal

        # parse-validate (the factory raises the naming ValueError);
        # the parsed signal is rebuilt at train() time, not stored here
        signal = make_signal(getattr(self, "preempt_signal", None))
        if signal is not None and not getattr(self, "checkpoint_dir", None):
            raise ValueError(
                f"preempt_signal={self.preempt_signal!r} needs "
                "checkpoint_dir: a preemption notice forces a durable "
                "checkpoint — with nowhere to land it the drained round "
                "would be lost"
            )
        raw = getattr(self, "elastic_min_devices", 1)
        try:
            self.elastic_min_devices = int(raw if raw is not None else 1)
        except (TypeError, ValueError):
            raise ValueError(
                f"elastic_min_devices={raw!r}: must be an integer >= 1"
            ) from None
        if self.elastic_min_devices < 1:
            raise ValueError(
                f"elastic_min_devices={self.elastic_min_devices}: must be "
                ">= 1 (the resume floor — below it the run refuses to "
                "continue)"
            )
        # -- defense / attack knobs (docs/robustness.md threat model) --
        defense = getattr(self, "defense_type", None) or None
        if defense is not None and defense not in constants.DEFENSE_TYPES:
            # the silent-no-defense footgun: a typo'd defense_type used
            # to fall through to a plain undefended mean
            raise ValueError(
                f"unknown defense_type {defense!r}; pick one of "
                f"{constants.DEFENSE_TYPES} (or null to disable)"
            )
        for float_key in (
            "norm_bound", "stddev", "defense_anomaly_threshold",
            "poisoned_client_fraction", "poison_sample_fraction",
        ):
            raw = getattr(self, float_key)
            try:
                setattr(self, float_key, float(raw))
            except (TypeError, ValueError):
                # a YAML `norm_bound: null` must name the knob, not
                # surface a bare float(None) TypeError
                raise ValueError(
                    f"{float_key}={raw!r}: must be a number"
                ) from None
        if self.norm_bound <= 0:
            raise ValueError(
                f"norm_bound={self.norm_bound}: must be > 0 (the clip "
                "radius around the global model)"
            )
        if self.stddev < 0:
            raise ValueError(f"stddev={self.stddev}: must be >= 0")
        if self.defense_anomaly_threshold < 0:
            raise ValueError(
                f"defense_anomaly_threshold={self.defense_anomaly_threshold}: "
                "must be >= 0 (0 disables the anomaly screen)"
            )
        raw = self.defense_quarantine_rounds
        try:
            self.defense_quarantine_rounds = int(raw)
        except (TypeError, ValueError):
            # same null-naming rule as the float knobs above
            raise ValueError(
                f"defense_quarantine_rounds={raw!r}: must be an integer"
            ) from None
        if self.defense_quarantine_rounds < 1:
            raise ValueError(
                f"defense_quarantine_rounds={self.defense_quarantine_rounds}: "
                "must be >= 1"
            )
        ptypes = getattr(self, "poison_type", None) or None
        if ptypes is not None:
            as_list = (
                list(ptypes) if isinstance(ptypes, (list, tuple)) else [ptypes]
            )
            bad = [t for t in as_list if t not in constants.POISON_TYPES]
            if bad:
                raise ValueError(
                    f"unknown poison_type {bad}; pick from "
                    f"{constants.POISON_TYPES}"
                )
            if isinstance(ptypes, (list, tuple)) and not (
                getattr(self, "poisoned_client_idxs", None)
            ):
                raise ValueError(
                    "poison_type as a list pairs 1:1 with "
                    "poisoned_client_idxs; set the idxs explicitly "
                    "(poisoned_client_fraction draws an arbitrary "
                    "attacker set)"
                )
        if not 0.0 <= self.poisoned_client_fraction <= 1.0:
            raise ValueError(
                f"poisoned_client_fraction={self.poisoned_client_fraction}: "
                "must be in [0, 1]"
            )
        if not 0.0 < self.poison_sample_fraction <= 1.0:
            raise ValueError(
                f"poison_sample_fraction={self.poison_sample_fraction}: "
                "must be in (0, 1]"
            )
        self.target_label = int(getattr(self, "target_label", 0) or 0)
        if self.serve_queue_size < 1 or self.serve_max_batch < 1:
            raise ValueError(
                f"serve_queue_size={self.serve_queue_size} / "
                f"serve_max_batch={self.serve_max_batch}: both must be >= 1"
            )
        for nonneg_key in (
            "serve_batch_wait_ms", "serve_deadline_ms", "serve_watch_interval_s",
            "serve_route_slo_ms", "serve_route_failover",
        ):
            if getattr(self, nonneg_key) < 0:
                raise ValueError(
                    f"{nonneg_key}={getattr(self, nonneg_key)}: must be >= 0"
                )
        if getattr(self, "serve_bucket", "pow2") not in ("pow2", "exact"):
            raise ValueError(
                f"serve_bucket {self.serve_bucket!r}: pick 'pow2' or 'exact'"
            )
        if self.serve_fleet_size < 1:
            raise ValueError(
                f"serve_fleet_size={self.serve_fleet_size}: must be >= 1 "
                "(1 = single endpoint, no fleet layer)"
            )
        if getattr(self, "serve_route_policy", "least_loaded") not in (
            "least_loaded", "static",
        ):
            raise ValueError(
                f"serve_route_policy {self.serve_route_policy!r}: pick "
                "'least_loaded' or 'static'"
            )
        serve_mesh = getattr(self, "serve_mesh", None)
        if serve_mesh is not None:
            if not isinstance(serve_mesh, dict) or not set(
                serve_mesh
            ) <= {"data", "fsdp"}:
                raise ValueError(
                    f"serve_mesh={serve_mesh!r}: expected a dict with "
                    "'data'/'fsdp' axis sizes (e.g. {'data': 2, 'fsdp': 2})"
                )
            self.serve_mesh = {k: int(v) for k, v in serve_mesh.items()}
        if getattr(self, "stall_timeout_s", 0.0) < 0:
            raise ValueError(
                f"stall_timeout_s={self.stall_timeout_s}: must be >= 0 "
                "(0 disables the stall watchdog)"
            )
        raw = getattr(self, "max_clients")
        try:
            # a YAML `max_clients: null` must name the knob (the
            # defense-knob convention), never coerce silently
            self.max_clients = int(raw)
        except (TypeError, ValueError):
            raise ValueError(
                f"max_clients={raw!r}: must be an integer"
            ) from None
        if self.max_clients < 1:
            raise ValueError(
                f"max_clients={self.max_clients}: must be >= 1"
            )
        raw = getattr(self, "compile_cache_dir", None)
        if raw is not None and not isinstance(raw, (str, os.PathLike)):
            # the null-naming rule: a YAML `compile_cache_dir: 3` must
            # name the knob, never surface inside jax.config
            raise ValueError(
                f"compile_cache_dir={raw!r}: must be a directory path "
                "(or null to disable the persistent compilation cache)"
            )
        raw = getattr(self, "checkpoint_freq")
        if raw is not None:  # None = the scenario's historical cadence
            try:
                self.checkpoint_freq = int(raw)
            except (TypeError, ValueError):
                raise ValueError(
                    f"checkpoint_freq={raw!r}: must be an integer (or "
                    "null for the scenario default)"
                ) from None
            if self.checkpoint_freq < 1:
                raise ValueError(
                    f"checkpoint_freq={self.checkpoint_freq}: must be >= 1"
                )
        for int_key in ("trace_ring_size", "devtime_ring_size", "metrics_port"):
            setattr(self, int_key, int(getattr(self, int_key)))
        if self.trace_ring_size < 1:
            raise ValueError(
                f"trace_ring_size={self.trace_ring_size}: must be >= 1"
            )
        if self.devtime_ring_size < 1:
            raise ValueError(
                f"devtime_ring_size={self.devtime_ring_size}: must be >= 1"
            )
        if not 0 <= self.metrics_port <= 65535:
            raise ValueError(
                f"metrics_port={self.metrics_port}: must be a port number "
                "(0 disables the /metrics server)"
            )
        self.round_deadline_s = float(self.round_deadline_s)
        if self.round_deadline_s < 0:
            raise ValueError(
                f"round_deadline_s={self.round_deadline_s}: must be >= 0 "
                "(0 disables the round SLO)"
            )
        pr = getattr(self, "profile_rounds", None)
        if pr is not None and not isinstance(pr, (str, list, tuple)):
            raise ValueError(
                f"profile_rounds={pr!r}: pass a list of round indices or "
                "a comma-separated string"
            )
        # -- planet-scale population plane (fedml_tpu/scale/) ----------
        for int_key in ("client_registry_size", "cohort_size", "edge_num"):
            raw = getattr(self, int_key)
            try:
                setattr(self, int_key, int(raw or 0))
            except (TypeError, ValueError):
                raise ValueError(
                    f"{int_key}={raw!r}: must be an integer"
                ) from None
            if getattr(self, int_key) < 0:
                raise ValueError(
                    f"{int_key}={getattr(self, int_key)}: must be >= 0 "
                    "(0 disables)"
                )
        if self.client_registry_size > 0:
            if t != constants.FEDML_TRAINING_PLATFORM_SIMULATION:
                raise ValueError(
                    "client_registry_size applies to training_type="
                    "simulation only (the cross-silo edge tier is the "
                    f"edge_num knob); got training_type={t!r}"
                )
            cohort = self.cohort_size or self.client_num_per_round
            if cohort > self.client_registry_size:
                raise ValueError(
                    f"cohort_size={cohort} exceeds "
                    f"client_registry_size={self.client_registry_size}"
                )
            if self.edge_num > cohort:
                raise ValueError(
                    f"edge_num={self.edge_num} exceeds the cohort size "
                    f"{cohort}: an edge tier wider than its cohort is a "
                    "misconfiguration, not a topology"
                )
        # -- hierarchical server plane (cross_silo/hierarchical) -------
        plane = str(getattr(self, "edge_plane", "inproc") or "inproc")
        if plane not in ("inproc", "ranks"):
            raise ValueError(
                f"edge_plane={plane!r}: pick 'inproc' (the in-process "
                "tree) or 'ranks' (edge aggregators as real ranks)"
            )
        self.edge_plane = plane
        raw_stride = getattr(self, "hier_port_stride", 64)
        try:
            self.hier_port_stride = int(
                64 if raw_stride is None else raw_stride
            )
        except (TypeError, ValueError):
            raise ValueError(
                f"hier_port_stride={raw_stride!r}: must be an integer"
            ) from None
        if self.hier_port_stride < 1:
            raise ValueError(
                f"hier_port_stride={self.hier_port_stride}: must be >= 1"
            )
        if plane == "ranks":
            if t != constants.FEDML_TRAINING_PLATFORM_CROSS_SILO:
                raise ValueError(
                    "edge_plane=ranks needs training_type=cross_silo "
                    f"(real edge processes over the comm seam); got {t!r}"
                )
            if getattr(self, "agg_mode", "stream") != "stream":
                raise ValueError(
                    "edge_plane=ranks requires agg_mode=stream: the edge "
                    "tier IS the streaming fold (one merged limb-set per "
                    "round crosses the root link); buffered has no "
                    "limb-set to ship and async hierarchy is ROADMAP work"
                )
            if self.edge_num < 1:
                raise ValueError(
                    f"edge_plane=ranks needs edge_num >= 1; got "
                    f"{self.edge_num}"
                )
            if self.edge_num > int(self.client_num_per_round):
                raise ValueError(
                    f"edge_num={self.edge_num} exceeds "
                    f"client_num_per_round={self.client_num_per_round}: an "
                    "edge tier wider than its clients is a "
                    "misconfiguration, not a topology"
                )
            if getattr(self, "defense_type", None) == constants.DEFENSE_MEDIAN:
                raise ValueError(
                    "edge_plane=ranks cannot run defense_type=median: a "
                    "full-cohort reduction needs every upload in one "
                    "place, which is exactly what the edge tier removes"
                )
            if bool(getattr(self, "elastic_membership", False)):
                raise ValueError(
                    "edge_plane=ranks does not support elastic_membership "
                    "yet: the client->edge partition is planned per run "
                    "(joins would need repartitioning)"
                )
            if float(getattr(self, "aggregation_deadline_s", 0) or 0) > 0:
                raise ValueError(
                    "edge_plane=ranks closes rounds per edge and uses the "
                    "quorum close at the root (round_quorum_frac/"
                    "round_grace_s); aggregation_deadline_s does not apply"
                )
        # -- cross-device Beehive check-in plane (cross_device/) -------
        for int_key in ("crossdevice_cohort", "crossdevice_mask_threshold",
                        "crossdevice_duty_hours"):
            raw = getattr(self, int_key)
            try:
                setattr(self, int_key, int(raw or 0))
            except (TypeError, ValueError):
                raise ValueError(
                    f"{int_key}={raw!r}: must be an integer"
                ) from None
        if self.crossdevice_cohort < 0:
            raise ValueError(
                f"crossdevice_cohort={self.crossdevice_cohort}: must be "
                ">= 0 (0 = client_num_per_round)"
            )
        if self.crossdevice_mask_threshold < 1:
            raise ValueError(
                f"crossdevice_mask_threshold="
                f"{self.crossdevice_mask_threshold}: must be >= 1 "
                "(shares needed to reconstruct a vanished device's mask)"
            )
        if not 1 <= self.crossdevice_duty_hours <= 24:
            raise ValueError(
                f"crossdevice_duty_hours={self.crossdevice_duty_hours}: "
                "must be in [1, 24] (hours per day a device is reachable)"
            )
        for float_key in ("crossdevice_fold_target_frac",
                          "crossdevice_report_window_s",
                          "crossdevice_quant_scale"):
            raw = getattr(self, float_key)
            try:
                setattr(self, float_key, float(raw))
            except (TypeError, ValueError):
                raise ValueError(
                    f"{float_key}={raw!r}: must be a number"
                ) from None
        if not 0.0 < self.crossdevice_fold_target_frac <= 1.0:
            raise ValueError(
                f"crossdevice_fold_target_frac="
                f"{self.crossdevice_fold_target_frac}: must be in (0, 1] "
                "(fraction of the offered cohort whose folds close a round)"
            )
        if self.crossdevice_report_window_s <= 0:
            raise ValueError(
                f"crossdevice_report_window_s="
                f"{self.crossdevice_report_window_s}: must be > 0"
            )
        if self.crossdevice_quant_scale <= 0:
            raise ValueError(
                f"crossdevice_quant_scale={self.crossdevice_quant_scale}: "
                "must be > 0"
            )
        self.crossdevice_secure_agg = bool(self.crossdevice_secure_agg)
        self.crossdevice_verify_pubkey = bool(self.crossdevice_verify_pubkey)

    # -- niceties ------------------------------------------------------
    def get(self, key: str, default: Any = None) -> Any:
        return getattr(self, key, default)

    def to_dict(self) -> Dict[str, Any]:
        return {k: v for k, v in self.__dict__.items() if not k.startswith("_")}

    def __repr__(self) -> str:  # pragma: no cover
        keys = ", ".join(sorted(self.to_dict()))
        return f"Arguments({keys})"


def add_args(parser: Optional[argparse.ArgumentParser] = None) -> argparse.Namespace:
    """The reference's two-flag CLI (arguments.py:32-49)."""
    parser = parser or argparse.ArgumentParser(description="fedml_tpu")
    parser.add_argument(
        "--yaml_config_file",
        "--cf",
        help="yaml configuration file",
        type=str,
        default="",
    )
    parser.add_argument("--rank", type=int, default=0)
    parser.add_argument("--local_rank", type=int, default=0)
    parser.add_argument("--role", type=str, default="client")
    parser.add_argument("--run_id", type=str, default="0")
    args, _ = parser.parse_known_args()
    return args


def _default_config_path(training_type: str) -> Optional[str]:
    name = {
        constants.FEDML_TRAINING_PLATFORM_SIMULATION: "simulation_sp.yaml",
        constants.FEDML_TRAINING_PLATFORM_CROSS_SILO: "cross_silo.yaml",
        constants.FEDML_TRAINING_PLATFORM_CROSS_DEVICE: "cross_device.yaml",
    }.get(training_type)
    if name is None:
        return None
    p = Path(__file__).parent / "config" / name
    return str(p) if p.exists() else None


def load_arguments(
    training_type: Optional[str] = None,
    comm_backend: Optional[str] = None,
) -> Arguments:
    """Entry point mirroring ``load_arguments`` (arguments.py:143-151)."""
    cmd_args = add_args()
    if not cmd_args.yaml_config_file:
        default = _default_config_path(
            training_type or _DEFAULTS["training_type"]
        )
        if default is not None and os.path.exists(default):
            cmd_args.yaml_config_file = default
    return Arguments(cmd_args, training_type, comm_backend)
