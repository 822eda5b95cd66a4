"""Device plane: a population of flaky phones behind one host rank.

``DeviceHost`` simulates every device of a round's cohort from the
columnar ``scale.ClientRegistry`` (availability phase, speed tier, seed
— bytes per device, no objects) and speaks the Beehive check-in
protocol to the gateway as rank 1 of a two-rank comm fabric
(``core/managers``). One host rank is the simulation seam only: every
device acts solely on its OWN registry row plus the round offer, and
the per-device messages it emits are exactly what a real phone would
send — the gateway cannot tell the difference, which is the point.

Churn is consulted, not suffered: before each protocol step a device
asks the chaos plane (``core.chaos.device_event``) whether it is
scheduled to vanish (skip the step — or, with ``after_close``, deliver
the upload after the round closed) or to later reveal a poisoned Shamir
share (``bad_share``). A vanish is normal operation here, never an
exception path.

Training compiles per DEVICE CLASS, not per device: the cohort's
participants are grouped by speed tier, each tier padded to a pow2
bucket (``core.bucketing``), and one jitted vmap serves each
(tier, bucket) shape — the compile census a million-device population
presents is the tier x bucket product, asserted in the tests. Tier t
runs ``t + 1`` local epochs (the device-class work scaling), so each
tier is its own executable by construction.
"""

from __future__ import annotations

import logging
import math
from typing import Any, Dict, List, Optional, Set, Tuple

import numpy as np

from .. import constants
from ..core.bucketing import bucket_cohort, pad_cohort_idx
from ..core.chaos import device_event
from ..core.managers import ClientManager
from ..core.message import Message
from ..core.secure_agg import (
    FIELD_PRIME,
    derive_mask_secret,
    field_checksum,
    mask_public_key,
    pairwise_mask_vector,
    quantize,
    shamir_share,
)
from .protocol import (
    decode_offer_params,
    pack_reveals,
    unpack_participants,
)

Params = Any

__all__ = ["DeviceHost"]


class DeviceHost(ClientManager):
    """Rank 1 of the Beehive fabric: the whole device population.

    Drives ``rounds`` check-in rounds against the gateway and then
    exits its receive loop. Exposes the compile census
    (``trace_count`` / ``shape_keys``) that tests/test_beehive.py
    asserts on.
    """

    def __init__(
        self,
        args,
        registry,
        feature_dim: int,
        class_num: int,
        rounds: int,
        cohort_size: int,
        rank: int = 1,
        size: int = 2,
        backend: str = constants.COMM_BACKEND_LOCAL,
    ) -> None:
        super().__init__(args, None, rank, size, backend)
        self.registry = registry
        self.feature_dim = int(feature_dim)
        self.class_num = int(class_num)
        self.rounds = int(rounds)
        self.cohort_size = int(cohort_size)
        self.secure_agg = bool(getattr(args, "crossdevice_secure_agg", True))
        self.threshold = int(getattr(args, "crossdevice_mask_threshold", 2))
        self.lr = float(getattr(args, "learning_rate", 0.1))
        self.batch_size = int(getattr(args, "batch_size", 16))
        # every device trains its full (clipped) sample count: one
        # fixed batch census per world, so shape variety comes only
        # from the (tier, bucket) axes
        self.num_batches = max(
            1, math.ceil(registry.max_samples / self.batch_size)
        )
        # compile census: one jitted vmap per tier (epochs = tier + 1
        # is a static python int), retraced per pow2 bucket shape
        self._tier_fns: Dict[int, Any] = {}
        # appended at trace time by the tier fns (one entry per
        # executable built); a plain list so the jitted closures never
        # capture `self`
        self._trace_events: list = []
        self.shape_keys: Set[Tuple[int, int]] = set()
        # per-round device-side state, cleared at ROUND_RESULT:
        # mask secrets by device, Shamir shares by HOLDER (a holder
        # reveals only what it was dealt — the gateway never sees a
        # secret that was not reconstructed from t+1 reveals)
        self._secrets: Dict[int, int] = {}
        self._held: Dict[int, Dict[int, Tuple[int, int]]] = {}
        self._bad_share: Set[int] = set()
        self._round_idx = -1

    # -- protocol wiring ----------------------------------------------
    def register_message_receive_handlers(self) -> None:
        self.register_message_receive_handler(
            constants.MSG_TYPE_CONNECTION_IS_READY, self._on_connect
        )
        self.register_message_receive_handler(
            constants.MSG_TYPE_S2D_ROUND_OFFER, self._on_offer
        )
        self.register_message_receive_handler(
            constants.MSG_TYPE_S2D_SHARE_REQUEST, self._on_share_request
        )
        self.register_message_receive_handler(
            constants.MSG_TYPE_S2D_ROUND_RESULT, self._on_result
        )

    def _send(self, msg_type: int, fields: Dict[str, Any]) -> None:
        msg = Message(msg_type, self.rank, 0)
        for k, v in fields.items():
            msg.add_params(k, v)
        self.send_message(msg)

    # -- round choreography -------------------------------------------
    def _on_connect(self, _msg: Message) -> None:
        self._begin_round(0)

    def _begin_round(self, round_idx: int) -> None:
        """Check-in window: every sampled, currently-available device
        either checks in (id + mask pubkey, nothing else — the server
        keeps no channel to it) or was scheduled to vanish and simply
        does not."""
        self._round_idx = round_idx
        self._secrets.clear()
        self._held.clear()
        self._bad_share.clear()
        cohort = self.registry.sample_available_cohort(
            round_idx, self.cohort_size
        )
        for did in (int(d) for d in cohort):
            fault = device_event("device.checkin", did, round_idx)
            if fault is not None and fault["kind"] == "vanish":
                continue  # churn: a no-show costs nobody anything
            pub = 0
            if self.secure_agg:
                secret = derive_mask_secret(
                    int(self.registry.client_seed[did]), round_idx
                )
                self._secrets[did] = secret
                pub = mask_public_key(secret)
            self._send(
                constants.MSG_TYPE_D2S_DEVICE_CHECKIN,
                {
                    constants.MSG_ARG_KEY_ROUND_INDEX: round_idx,
                    constants.MSG_ARG_KEY_DEVICE_ID: did,
                    constants.MSG_ARG_KEY_DEVICE_PUBKEY: int(pub),
                },
            )
        self._send(
            constants.MSG_TYPE_D2S_WINDOW_TICK,
            {
                constants.MSG_ARG_KEY_ROUND_INDEX: round_idx,
                constants.MSG_ARG_KEY_WINDOW_PHASE: (
                    constants.DEVICE_WINDOW_CHECKIN
                ),
            },
        )

    @property
    def trace_count(self) -> int:
        """Executables actually traced — must equal ``len(shape_keys)``
        (one jit trace per (tier, bucket) shape)."""
        return len(self._trace_events)

    # -- per-(tier, bucket) compiled training -------------------------
    def _tier_fn(self, tier: int):
        fn = self._tier_fns.get(int(tier))
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp

        epochs = int(tier) + 1
        lr = self.lr

        def loss_fn(p, xb, yb, mb):
            logits = xb @ p["w"] + p["b"]
            logp = jax.nn.log_softmax(logits)
            nll = -jnp.take_along_axis(
                logp, yb[:, None].astype(jnp.int32), axis=1
            )[:, 0]
            return (nll * mb).sum() / jnp.maximum(mb.sum(), 1.0)

        def train_one(params, x, y, mask):
            def batch_step(p, batch):
                xb, yb, mb = batch
                g = jax.grad(loss_fn)(p, xb, yb, mb)
                return jax.tree.map(lambda w, gw: w - lr * gw, p, g), None

            def epoch(p, _):
                p, _ = jax.lax.scan(batch_step, p, (x, y, mask))
                return p, None

            p, _ = jax.lax.scan(epoch, params, None, length=epochs)
            return p

        trace_events = self._trace_events

        def group_fn(params, x, y, mask):
            # fires at trace time only: the census of (tier, bucket)
            # executables, same idiom as scale/engine's round fn
            trace_events.append(epochs)
            return jax.vmap(train_one, in_axes=(None, 0, 0, 0))(
                params, x, y, mask
            )

        fn = jax.jit(group_fn)
        self._tier_fns[int(tier)] = fn
        return fn

    def _train_cohort(
        self, global_params: Params, part_ids: np.ndarray
    ) -> Tuple[Dict[int, np.ndarray], Dict[int, int]]:
        """Train every participant, grouped by speed tier and padded to
        pow2 buckets. Returns per-device flat deltas (leaf order =
        ``flatten_params``'s) and per-device packed sample counts."""
        import jax

        deltas: Dict[int, np.ndarray] = {}
        samples: Dict[int, int] = {}
        tiers = self.registry.speed_tier[part_ids]
        for tier in sorted(int(t) for t in np.unique(tiers)):
            tier_ids = part_ids[tiers == tier]
            bucket = bucket_cohort(len(tier_ids), "pow2")
            padded, valid = pad_cohort_idx(tier_ids, bucket)
            self.shape_keys.add((tier, bucket))
            batches, ns = self.registry.materialize_group(
                padded, self.num_batches, self.batch_size,
                (self.feature_dim,), self.class_num,
            )
            stacked = self._tier_fn(tier)(
                global_params, batches.x, batches.y, batches.mask
            )
            delta = jax.tree.map(
                lambda s, g: np.asarray(s) - np.asarray(g)[None],
                stacked, global_params,
            )
            leaves = jax.tree.leaves(delta)
            flat = np.concatenate(
                [l.reshape(bucket, -1) for l in leaves], axis=1
            ).astype(np.float64)
            for slot, did in enumerate(int(d) for d in tier_ids):
                deltas[did] = flat[slot]
                samples[did] = int(ns[slot])
        return deltas, samples

    # -- the report window --------------------------------------------
    def _on_offer(self, msg: Message) -> None:
        round_idx = int(msg.get(constants.MSG_ARG_KEY_ROUND_INDEX))
        participants = unpack_participants(
            msg.get(constants.MSG_ARG_KEY_PARTICIPANTS)
        )
        scale = float(msg.get(constants.MSG_ARG_KEY_QUANT_SCALE))
        part_ids = np.fromiter(sorted(participants), dtype=np.int64)
        late_uploads: List[Message] = []
        if len(part_ids):
            global_params = decode_offer_params(
                msg.get(Message.MSG_ARG_KEY_MODEL_PARAMS)
            )
            deltas, samples = self._train_cohort(global_params, part_ids)
            dim = next(iter(deltas.values())).shape[0]
            if self.secure_agg:
                self._deal_shares(round_idx, part_ids)
            for did in (int(d) for d in part_ids):
                q = quantize(deltas[did] * samples[did], scale)
                if self.secure_agg:
                    q = np.mod(
                        q + pairwise_mask_vector(
                            did, self._secrets[did], participants, dim
                        ),
                        FIELD_PRIME,
                    )
                upload = Message(
                    constants.MSG_TYPE_D2S_MASKED_UPLOAD, self.rank, 0
                )
                upload.add_params(constants.MSG_ARG_KEY_ROUND_INDEX, round_idx)
                upload.add_params(constants.MSG_ARG_KEY_DEVICE_ID, did)
                upload.add_params(constants.MSG_ARG_KEY_MASKED_DELTA, q)
                upload.add_params(
                    constants.MSG_ARG_KEY_MASK_CHECKSUM, field_checksum(q)
                )
                upload.add_params(
                    Message.MSG_ARG_KEY_NUM_SAMPLES, samples[did]
                )
                fault = device_event("device.upload", did, round_idx)
                kind = None if fault is None else fault["kind"]
                if kind == "bad_share":
                    # uploads fine NOW; poisons any share it reveals
                    # later for a vanished masker
                    self._bad_share.add(did)
                elif kind == "vanish":
                    if fault.get("after_close"):
                        late_uploads.append(upload)  # arrives post-close
                    continue  # churn: the upload never happens
                self.send_message(upload)
        self._send(
            constants.MSG_TYPE_D2S_WINDOW_TICK,
            {
                constants.MSG_ARG_KEY_ROUND_INDEX: round_idx,
                constants.MSG_ARG_KEY_WINDOW_PHASE: (
                    constants.DEVICE_WINDOW_REPORT
                ),
            },
        )
        # the after_close flavor: the delta was computed in time but the
        # phone's radio came back after the window — FedBuff food
        for upload in late_uploads:
            self.send_message(upload)

    def _deal_shares(self, round_idx: int, part_ids: np.ndarray) -> None:
        """Every participant Shamir-shares its round secret to the full
        roster (device-to-device; the gateway holds NO share). Holder at
        roster position k receives the share at point k+1."""
        n = len(part_ids)
        t = min(self.threshold, max(1, n - 1))
        for owner in (int(d) for d in part_ids):
            rng = np.random.default_rng(
                (int(self.registry.client_seed[owner]) * 31
                 + round_idx * 7 + 3) % (2**32)
            )
            shares = shamir_share(
                np.asarray(self._secrets[owner], dtype=np.int64), n, t, rng
            )
            for pos, holder in enumerate(int(d) for d in part_ids):
                if holder == owner:
                    continue
                self._held.setdefault(holder, {})[owner] = (
                    pos + 1, int(shares[pos]),
                )

    def _on_share_request(self, msg: Message) -> None:
        """Dropout recovery: survivors reveal their shares of each
        vanished masker's secret. A ``bad_share`` device reveals a
        perturbed value — the planted-fault seam the pubkey
        verification upstream must catch."""
        round_idx = int(msg.get(constants.MSG_ARG_KEY_ROUND_INDEX))
        vanished = np.asarray(
            msg.get(constants.MSG_ARG_KEY_DEVICE_ID), dtype=np.int64
        )
        folded = np.asarray(
            msg.get(constants.MSG_ARG_KEY_PARTICIPANTS), dtype=np.int64
        )
        reveals: Dict[int, List[Tuple[int, int]]] = {}
        for v in (int(x) for x in vanished):
            pairs: List[Tuple[int, int]] = []
            for holder in (int(h) for h in folded):
                entry = self._held.get(holder, {}).get(v)
                if entry is None:
                    continue
                point, value = entry
                if holder in self._bad_share:
                    value = (value + 1) % FIELD_PRIME
                pairs.append((point, value))
            reveals[v] = pairs
        self._send(
            constants.MSG_TYPE_D2S_SHARE_REVEAL,
            {
                constants.MSG_ARG_KEY_ROUND_INDEX: round_idx,
                constants.MSG_ARG_KEY_SHARE_REVEALS: pack_reveals(reveals),
            },
        )

    def _on_result(self, msg: Message) -> None:
        round_idx = int(msg.get(constants.MSG_ARG_KEY_ROUND_INDEX))
        if round_idx + 1 < self.rounds:
            self._begin_round(round_idx + 1)
        else:
            logging.info("device host: %d rounds done", self.rounds)
            self.finish()
