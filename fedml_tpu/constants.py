"""Framework-wide constants.

Parity with the reference's ``python/fedml/constants.py`` (scenario names,
partition methods, backend names), extended with TPU-native backends.
"""

# MNIST LEAF archive (reference constants.py:18; data/MNIST/
# data_loader.py:17-29 downloads + extracts it)
FEDML_DATA_MNIST_URL = "https://fedcv.s3.us-west-1.amazonaws.com/MNIST.zip"

FEDML_TRAINING_PLATFORM_SIMULATION = "simulation"
FEDML_TRAINING_PLATFORM_CROSS_SILO = "cross_silo"
FEDML_TRAINING_PLATFORM_CROSS_DEVICE = "cross_device"
FEDML_TRAINING_PLATFORM_DISTRIBUTED = "distributed"

# Simulation sub-backends (reference: simulation/simulator.py:28,43,100).
# The reference's NCCL simulator is a stub; here "MESH" is the real thing —
# simulated clients are sharded over a jax.sharding.Mesh and aggregation
# rides ICI collectives.
FEDML_SIMULATION_TYPE_SP = "single_process"
FEDML_SIMULATION_TYPE_MESH = "MESH"
FEDML_SIMULATION_TYPE_NCCL = "NCCL"  # accepted as an alias of MESH

# Cross-silo scenario hierarchy (reference: constants.py CROSS_SILO_SCENARIO_*)
FEDML_CROSS_SILO_SCENARIO_HORIZONTAL = "horizontal"
FEDML_CROSS_SILO_SCENARIO_HIERARCHICAL = "hierarchical"

# Communication backends (reference: client_manager.py:27-94 dispatch table).
COMM_BACKEND_LOCAL = "LOCAL"  # in-process queues (tests / single host)
COMM_BACKEND_GRPC = "GRPC"
COMM_BACKEND_TRPC = "TRPC"  # persistent-pipe raw-tensor RPC (TensorPipe analog)
COMM_BACKEND_MPI = "MPI"  # accepted; mapped onto the LOCAL/GRPC transports
COMM_BACKEND_MQTT = "MQTT"
COMM_BACKEND_MQTT_S3 = "MQTT_S3"
COMM_BACKEND_SP = "sp"
COMM_BACKEND_MESH = "MESH"

# Data partition methods (reference: data/cifar10/data_loader.py:122-183)
PARTITION_HOMO = "homo"
PARTITION_HETERO = "hetero"
PARTITION_HETERO_FIX = "hetero-fix"

# Robust-aggregation defenses (reference robust_aggregation.py:41-99)
# and the poisoning attacks they defend against (reference
# data/edge_case_examples/data_loader.py; data/poison.py reproduces the
# mechanisms). ONE authoritative vocabulary: knob validation
# (arguments.py), RobustAggregator construction, needs_full_cohort and
# the poisoned-world loader all check against these — an unknown string
# fails loudly everywhere instead of silently aggregating undefended.
DEFENSE_NORM_DIFF_CLIPPING = "norm_diff_clipping"
DEFENSE_WEAK_DP = "weak_dp"
DEFENSE_MEDIAN = "median"
DEFENSE_TYPES = (DEFENSE_NORM_DIFF_CLIPPING, DEFENSE_WEAK_DP, DEFENSE_MEDIAN)
POISON_TYPES = ("label_flip", "targeted_flip", "backdoor_pattern", "edge_case")

# Federated optimizers
FED_OPTIMIZER_FEDAVG = "FedAvg"
FED_OPTIMIZER_FEDOPT = "FedOpt"
FED_OPTIMIZER_FEDPROX = "FedProx"
FED_OPTIMIZER_FEDNOVA = "FedNova"

# Message-protocol constants shared by all FedAvg-family managers
# (reference: simulation/mpi_p2p_mp/fedavg/message_define.py:1-31).
MSG_TYPE_S2C_INIT_CONFIG = 1
MSG_TYPE_S2C_SYNC_MODEL_TO_CLIENT = 2
MSG_TYPE_C2S_SEND_MODEL_TO_SERVER = 3
MSG_TYPE_C2S_CLIENT_STATUS = 5
MSG_TYPE_S2C_FINISH = 7
MSG_TYPE_C2S_FINISH_ACK = 8
MSG_TYPE_CONNECTION_IS_READY = 0

# Liveness + crash-recovery protocol (core/comm/heartbeat.py and the
# cross-silo managers — beyond the reference, which has no failure
# detection): clients emit periodic HEARTBEATs; a server that misses
# them past heartbeat_timeout_s declares the client dead. RESYNC is the
# reconnect downlink — current round + params + silo assignment — sent
# to a client that (re)appears mid-federation or after a server
# restart, instead of a stale round-0 init.
MSG_TYPE_C2S_HEARTBEAT = 9
MSG_TYPE_S2C_RESYNC = 10

MSG_ARG_KEY_TYPE = "msg_type"
MSG_ARG_KEY_SENDER = "sender"
MSG_ARG_KEY_RECEIVER = "receiver"
MSG_ARG_KEY_MODEL_PARAMS = "model_params"
MSG_ARG_KEY_NUM_SAMPLES = "num_samples"
MSG_ARG_KEY_CLIENT_INDEX = "client_idx"
MSG_ARG_KEY_CLIENT_STATUS = "client_status"
MSG_ARG_KEY_ROUND_INDEX = "round_idx"
MSG_ARG_KEY_MODEL_FILE_URL = "model_file_url"
# compressed-uplink protocol (core/compression.py — beyond the
# reference): encoded update delta instead of full model_params
MSG_ARG_KEY_MODEL_DELTA = "model_delta"

CLIENT_STATUS_ONLINE = "ONLINE"
CLIENT_STATUS_IDLE = "IDLE"
CLIENT_STATUS_OFFLINE = "OFFLINE"  # elastic leave (beyond the reference)

# Hierarchical cross-silo intra-silo control plane (reference:
# cross_silo/hierarchical/client_master_manager.py:239-249 broadcasts
# [round_idx, model, client_index] via dist.broadcast_object_list; here
# the same triple travels as a message on a silo-private fabric).
MSG_TYPE_SILO_SYNC_PROCESS_GROUP = 20
MSG_TYPE_SILO_FINISH = 21

# server-internal: aggregation deadline fired (straggler handling —
# beyond the reference, which always waits for every client)
MSG_TYPE_S2S_AGG_DEADLINE = 30
# server-internal: the failure detector declared a client dead (posted
# to the server's own inbox so membership mutation stays on the single
# dispatch thread, same pattern as the deadline loopback)
MSG_TYPE_S2S_CLIENT_DEAD = 31
# server-internal: the quorum grace timer fired (streaming aggregation,
# round_quorum_frac/round_grace_s — once a quorum of uploads has folded
# and the grace elapses, the round closes over the partial cohort; same
# loopback pattern as the deadline)
MSG_TYPE_S2S_QUORUM_GRACE = 32

# Serving plane (fedml_tpu/serving — beyond the reference, which ships
# trained models to an external MLOps tier): one request/response pair
# over any comm backend; the payload keys live on the frontends.
MSG_TYPE_C2S_INFER_REQUEST = 40
MSG_TYPE_S2C_INFER_RESPONSE = 41

# Reliable-delivery channel (core/comm/reliable.py): comm-layer ACKs
# that never reach application handlers — the channel consumes them.
# Tracked messages carry (channel-id, sequence) in their params; the
# ACK echoes both so a restarted process's fresh channel id can never
# collide with its previous incarnation's sequence space.
MSG_TYPE_COMM_ACK = 50
MSG_ARG_KEY_COMM_SEQ = "comm_seq"
MSG_ARG_KEY_COMM_CHAN = "comm_chan"
MSG_ARG_KEY_COMM_ACK_SEQ = "comm_ack_seq"
MSG_ARG_KEY_COMM_ACK_CHAN = "comm_ack_chan"
# failure-detector internals: which rank was declared dead
MSG_ARG_KEY_RANK = "rank"

# Distributed-tracing context (core/tracing.py — beyond the reference,
# which has no cross-process causality at all): every tracked message
# carries W3C-style trace context so a broadcast → local-train → upload
# → aggregate chain is one causally-linked trace across processes and
# backends. ``TRACE_ID`` names the run-wide trace, ``TRACE_SPAN`` the
# sending span (the receiver's parent), ``TRACE_FLOW`` a per-wire-send
# unique id that pairs the Chrome-trace flow events (ph "s"/"f") the
# stitcher matches across shards. ``TRAIN_SECONDS`` rides on uploads so
# the server can attribute round time to client compute live (the
# stitched analyzer computes the precise version offline).
MSG_ARG_KEY_TRACE_ID = "trace_id"
MSG_ARG_KEY_TRACE_SPAN = "trace_span"
MSG_ARG_KEY_TRACE_FLOW = "trace_flow"
MSG_ARG_KEY_TRAIN_SECONDS = "train_seconds"

# Async (FedBuff-style) aggregation protocol (agg_mode=async — beyond
# the reference): the server never barriers on a cohort. Each downlink
# carries the publish VERSION its params came from; the client echoes
# it on the upload so the server can staleness-discount the update
# (``staleness_decay^(current - base)``). ``ROUND_INDEX`` doubles as a
# per-dispatch sequence id in async mode, which is what makes folds
# exactly-once attributable across retransmits and server restarts.
MSG_ARG_KEY_MODEL_VERSION = "model_version"

# Hierarchical server plane (cross_silo/hierarchical edge ranks —
# beyond the reference, whose "hierarchical" scenario is intra-silo
# process groups): edges are real ranks over the comm seam. The root
# reuses the S2C round downlinks (init/sync/resync) toward edges, with
# the per-client silo assignment map and the root's quarantine decision
# riding as extra params; the edge ships ONE merged limb-set (its
# streaming accumulator's exact 3-limb expansion + weights + folded
# set) upstream per round close, and forwards client death/leave/
# anomaly evidence as CLIENT_EVENTs — the root decides, edges enforce.
MSG_TYPE_E2R_EDGE_REPORT = 60
MSG_TYPE_E2R_CLIENT_EVENT = 61
MSG_ARG_KEY_EDGE_STATE = "edge_state"
MSG_ARG_KEY_HIER_ASSIGNMENT = "hier_assignment"
MSG_ARG_KEY_QUARANTINED = "quarantined"
MSG_ARG_KEY_EVENT_KIND = "event_kind"
MSG_ARG_KEY_COHORT = "cohort"
MSG_ARG_KEY_FOLDED = "folded"

# client-event kinds an edge reports upstream (root decides, edges
# enforce — docs/hierarchical.md failure model)
HIER_EVENT_DEAD = "dead"
HIER_EVENT_LEAVE = "leave"
HIER_EVENT_ONLINE = "online"
HIER_EVENT_QUARANTINE = "quarantine_evidence"

# Cross-device "Beehive" check-in protocol (fedml_tpu/cross_device/
# gateway.py + device.py, docs/cross_device.md — the connectionless
# churn-is-normal plane): a device CHECKs IN with its round-scoped mask
# public key, pulls the ROUND_OFFER (current round, int8-codec params,
# participant pubkeys, fold target + report window) if eligible, pushes
# ONE masked quantized delta, and disappears — no heartbeats, no
# failure detector. WINDOW_TICKs are the simulator's deterministic
# stand-in for wall-clock window expiry; SHARE_REQUEST/REVEAL is the
# dropout-recovery exchange (survivors reveal Shamir shares for
# vanished maskers); ROUND_RESULT announces a close so the device
# plane can advance. 70s decade.
MSG_TYPE_D2S_DEVICE_CHECKIN = 70
MSG_TYPE_S2D_ROUND_OFFER = 71
MSG_TYPE_D2S_MASKED_UPLOAD = 72
MSG_TYPE_D2S_WINDOW_TICK = 73
MSG_TYPE_S2D_SHARE_REQUEST = 74
MSG_TYPE_D2S_SHARE_REVEAL = 75
MSG_TYPE_S2D_ROUND_RESULT = 76
MSG_ARG_KEY_DEVICE_ID = "device_id"
MSG_ARG_KEY_DEVICE_PUBKEY = "device_pubkey"
MSG_ARG_KEY_MASKED_DELTA = "masked_delta"
MSG_ARG_KEY_MASK_CHECKSUM = "mask_checksum"
MSG_ARG_KEY_PARTICIPANTS = "participants"
MSG_ARG_KEY_QUANT_SCALE = "quant_scale"
MSG_ARG_KEY_SHARE_REVEALS = "share_reveals"
MSG_ARG_KEY_WINDOW_PHASE = "window_phase"
MSG_ARG_KEY_CLOSE_INFO = "close_info"

# report-window phases a WINDOW_TICK may close (the check-in window
# gathers participants; the report window bounds uploads)
DEVICE_WINDOW_CHECKIN = "checkin"
DEVICE_WINDOW_REPORT = "report"
# round close reasons the gateway ledgers (target reached vs window
# expired — never cohort completeness)
DEVICE_CLOSE_TARGET = "target"
DEVICE_CLOSE_WINDOW = "window"

# -- performance-attribution plane (analysis/perf.py) -----------------
# Per-chip peaks by device kind: bf16 matmul TFLOP/s and HBM TB/s. THE
# one table the package's roofline ridge comes from (`fedml-tpu perf`'s
# roofline join); the benchmark keeps its own in benchmark/peaks.json and
# tests/test_benchmark_seam.py holds the two v5e rows equal. A kind that is not here is
# an error (peak_bf16_flops / hbm_bandwidth_bytes raise) — never a
# silent 0. Keys are ``jax.devices()[0].device_kind`` strings; the chip
# this repo is checked on reports "TPU v5 lite" (chip_smoke.py, PR 21).
# Sources: Google Cloud TPU documentation, the system-architecture page
# of each generation ("TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM — the
# one row confirmed against the hardware at hand; v4, v5p and v6e rows
# are those pages' figures and have not been run here).
PEAK_BF16_TFLOPS = {
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
    "TPU v5p": 459.0,
    "TPU v6 lite": 918.0,
    "TPU v6e": 918.0,
}

HBM_BANDWIDTH_TBPS = {
    "TPU v4": 1.2,
    "TPU v5 lite": 0.819,
    "TPU v5e": 0.819,
    "TPU v5p": 2.765,
    "TPU v6 lite": 1.64,
    "TPU v6e": 1.64,
}


def normalize_device_kind(kind: str) -> str:
    """Canonical device-kind label for the ratchet's grouping:
    strips per-chip ordinals jax appends (``"TPU v5 lite0"`` ->
    ``"TPU v5 lite"``) and folds every CPU spelling (``TFRT_CPU_0``,
    ``cpu``, ``Cpu0``) to ``"cpu"`` so smoke records always group
    together and never ratchet against TPU captures."""
    k = str(kind or "").strip()
    if "cpu" in k.lower():
        return "cpu"
    # longest-match against the known table so "TPU v4i" never folds
    # into "TPU v4"; per-chip ordinal suffixes (digits) are tolerated
    best = ""
    low = k.lower()
    for name in PEAK_BF16_TFLOPS:
        nl = name.lower()
        if (low == nl or low.startswith(nl)) and len(name) > len(best):
            rest = low[len(nl):]
            if rest == "" or rest.isdigit():
                best = name
    return best or k


def _peak(table: dict, kind: str, what: str) -> float:
    canon = normalize_device_kind(kind)
    if canon not in table:
        raise ValueError(
            f"no {what} for device_kind {kind!r}: add it (with its "
            f"source) to fedml_tpu.constants; known: {sorted(table)}"
        )
    return table[canon] * 1e12


def peak_bf16_flops(kind: str) -> float:
    """Per-chip bf16 peak in FLOP/s for ``kind`` (device_kind string,
    ordinal suffix OK). An unknown kind raises: a caller that wants
    "no MFU on CPU" decides that from the platform before asking."""
    return _peak(PEAK_BF16_TFLOPS, kind, "bf16 peak")


def hbm_bandwidth_bytes(kind: str) -> float:
    """Per-chip HBM bandwidth in bytes/s; an unknown kind raises."""
    return _peak(HBM_BANDWIDTH_TBPS, kind, "HBM bandwidth")
