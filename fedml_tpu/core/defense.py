"""On-arrival anomaly screening and rank quarantine (S-FedAvg-style).

The robust-aggregation defenses (``core/aggregation.py``
``RobustAggregator`` + the streamable clipped term executables) bound
how much any single upload can move the global model. This module adds
the *identity* layer the reference fork's S-FedAvg line builds on:
score every upload the moment it lands, keep a per-rank reputation,
and quarantine ranks whose reputation crosses a threshold — their
uploads are rejected BEFORE folding and the rank is excluded from
subsequent cohorts until a probation expires.

Scores per upload (computed in one jitted pass over the delta):

- **norm excess** — how far the upload delta's L2 norm sits above the
  EWMA of recently accepted norms (attackers that try to dominate the
  mean ship outsized deltas; norm-diff clipping bounds the damage,
  the score attributes it);
- **cosine dissimilarity** — cosine of the upload delta to the running
  aggregate of the current window: poisoned objectives pull away from
  the honest consensus direction even when their norms look plausible.
  The first upload of a window has no running aggregate and gets a
  NEUTRAL cosine — deliberately: consecutive SGD rounds anti-correlate
  near convergence, so scoring the first arrival against the previous
  round's direction quarantines whoever happens to arrive first.

``anomaly_score`` combines the two into [0, ~2.5]; a per-rank EWMA of
that score (``reputation``) crossing ``defense_anomaly_threshold``
quarantines the rank for ``defense_quarantine_rounds`` round closes
(sync) or publishes (async). Release gives a fresh slate: a
misclassified honest rank recovers, a persistent attacker re-trips
within a couple of uploads.

Screening decisions are inherently **arrival-order dependent** (the
running aggregate is) — unlike the clipped fold itself, which stays
bitwise order-independent. The bit-identity guarantees therefore apply
to clipping/weak_dp configs with screening off (the default:
``defense_anomaly_threshold: 0``).
"""

from __future__ import annotations

import functools
import logging
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import constants
from .aggregation import global_norm

Params = Any


@jax.jit
def delta_from(theta: Params, g: Params) -> Params:
    """Upload minus broadcast global, in f32 — the tree every anomaly
    score is computed over."""
    return jax.tree.map(
        lambda t, gg: t.astype(jnp.float32) - gg.astype(jnp.float32), theta, g
    )


@functools.partial(jax.jit, static_argnums=0)
def decoded_delta(codec, encoded: Params, like: Params) -> Params:
    """Decode a compressed upload to its f32 delta for scoring
    (``like`` supplies shapes; used only when screening is on — the
    fold itself decodes inside its own fused executable)."""
    from .compression import decode_delta

    return jax.tree.map(
        lambda d: d.astype(jnp.float32), decode_delta(codec, encoded, like)
    )


@jax.jit
def _norm_and_cos(delta: Params, ref: Params):
    """(||delta||, cos(delta, ref)) in one pass."""
    n = global_norm(delta)
    rn = global_norm(ref)
    dot = sum(
        jnp.vdot(a, b)
        for a, b in zip(jax.tree.leaves(delta), jax.tree.leaves(ref))
    )
    return n, dot / jnp.maximum(n * rn, 1e-12)


def anomaly_score(
    norm: float, cos: Optional[float], ref_norm: Optional[float]
) -> float:
    """THE score combination — the unit oracle tests/test_defense.py
    pins against. Neutral inputs (no reference yet) score 0.

    The cosine evidence is weighted by the upload's *capacity to harm*
    (its norm relative to the cohort's reference norm): a converged
    honest client ships a small, directionally-noisy delta — noisy
    direction with no mass is not an attack, while an attacker must
    ship mass to move the mean and that mass keeps its full cosine
    evidence. ``ratio`` is capped so one enormous upload saturates
    rather than dominating the reputation forever."""
    ratio = 1.0 if not ref_norm else min(norm / ref_norm, 4.0)
    norm_score = max(ratio - 1.0, 0.0)
    cos_score = 0.0
    if cos is not None:
        cos_score = min(max(1.0 - cos, 0.0), 2.0) / 2.0
    return 0.5 * norm_score + 0.5 * min(ratio, 1.0) * cos_score


class AnomalyScreen:
    """Per-rank reputation + quarantine state for one aggregation
    endpoint. Keyed by AGGREGATOR INDEX (rank - 1), like every other
    per-client structure on the server. Enabled iff
    ``defense_anomaly_threshold > 0``."""

    #: EWMA step for the per-rank reputation. 0.4 means one outlier
    #: upload moves a clean rank to 0.4 x its score (a single honest
    #: spike stays under a ~0.5-x-spike threshold) while two
    #: consecutive quarantine-grade uploads reach 0.64 x score — an
    #: attacker's sustained signal trips within two uploads
    ALPHA = 0.4
    #: recent accepted-norm window; the reference magnitude is its
    #: MEDIAN — with an honest majority, attacker norms land in the
    #: tail and cannot drag the reference the way an EWMA mean would
    NORM_WINDOW = 16

    def __init__(self, args) -> None:
        from collections import deque

        self.threshold = float(
            getattr(args, "defense_anomaly_threshold", 0.0) or 0.0
        )
        self.quarantine_rounds = int(
            getattr(args, "defense_quarantine_rounds", 3)
        )
        self.enabled = self.threshold > 0
        self._rep: Dict[int, float] = {}
        self._quarantined: Dict[int, int] = {}  # idx -> periods left
        # quarantined during the CURRENT period: the tick that closes
        # the tripping round/publish must not count as served probation
        # (otherwise defense_quarantine_rounds=1 excludes zero cohorts)
        self._fresh: set = set()
        self._recent_norms = deque(maxlen=self.NORM_WINDOW)
        # absolute floor on the reference magnitude: once a federation
        # converges, accepted norms collapse toward zero and a RATIO
        # against a near-zero median would read any ordinary small step
        # as a 4x anomaly (measured: post-convergence honest uploads
        # insta-quarantined against a 0.001-norm median). With a
        # clipping defense the floor ties to the clip radius — a delta
        # far below the clip bound cannot move the aggregate anyway, so
        # it is never norm-anomalous. Screening WITHOUT clipping has no
        # clip radius to anchor on (norm_bound is an unused knob
        # there); the floor instead tracks the peak window median this
        # run has seen — honest-majority-robust (one accepted outlier
        # cannot move a median) and convergence-proof (norms only
        # collapse downward from the early-training scale).
        self.norm_floor = (
            0.25 * float(getattr(args, "norm_bound", 5.0))
            if (getattr(args, "defense_type", None) or None)
            in (
                constants.DEFENSE_NORM_DIFF_CLIPPING,
                constants.DEFENSE_WEAK_DP,
            )
            else None
        )
        self._peak_median = 0.0
        self.quarantines_total = 0

    @property
    def _ref_norm(self) -> Optional[float]:
        if not self._recent_norms:
            return None
        import statistics

        med = statistics.median(self._recent_norms)
        if self.norm_floor is not None:
            return max(med, self.norm_floor)
        self._peak_median = max(self._peak_median, med)
        return max(med, 0.25 * self._peak_median)

    # -- scoring ------------------------------------------------------
    def score_upload(
        self,
        delta: Params,
        running_ref: Optional[Params] = None,
        staleness: int = 0,
    ) -> Tuple[float, float, Optional[float]]:
        """(score, norm, cos) for one upload delta. ``running_ref`` is
        the current window's running aggregate direction; without one
        (first upload of the window) the cosine term is NEUTRAL — see
        the module docstring for why a stale cross-round direction must
        not substitute.

        **Staleness-aware** (async mode): an update trained against an
        old publish carries a catch-up delta spanning ~``staleness + 1``
        publishes of movement — its norm is EXPECTED to be larger, so
        the scored norm is normalized to ``norm / (1 + staleness)``
        before the excess test (a stale honest client reads as fresh;
        an attacker's outsized delta still stands out after the
        discount). The returned norm IS the normalized one — it also
        feeds the reference window, keeping the median comparable
        across staleness."""
        if running_ref is None:
            norm, cos = float(global_norm(delta)), None  # lint: host-sync-ok — the screen scores per upload on host by design
        else:
            n, c = _norm_and_cos(delta, running_ref)
            norm, cos = float(n), float(c)  # lint: host-sync-ok — the screen scores per upload on host by design
        norm = norm / (1.0 + max(int(staleness), 0))  # lint: host-sync-ok — staleness is a wire int
        return anomaly_score(norm, cos, self._ref_norm), norm, cos

    def observe(self, index: int, score: float, norm: float) -> bool:
        """Fold one upload's score into rank ``index``'s reputation
        (``norm`` is the staleness-normalized norm ``score_upload``
        returned). True -> the rank JUST crossed the threshold:
        quarantine it and reject this upload (the tripping upload never
        folds)."""
        rep = (1.0 - self.ALPHA) * self._rep.get(index, 0.0) + self.ALPHA * score
        self._rep[index] = rep
        if rep >= self.threshold:
            self._quarantined[index] = self.quarantine_rounds
            self._fresh.add(index)
            self.quarantines_total += 1
            # fresh slate on release: a misclassified honest rank
            # recovers; a persistent attacker re-trips in ~2 uploads
            self._rep[index] = 0.0
            logging.warning(
                "defense: rank index %d QUARANTINED for %d period(s) "
                "(reputation %.3f >= threshold %.3f; upload rejected)",
                index, self.quarantine_rounds, rep, self.threshold,
            )
            return True
        # accepted: this (staleness-normalized) norm extends the
        # reference-magnitude window
        self._recent_norms.append(norm)
        return False

    # -- quarantine lifecycle -----------------------------------------
    def is_quarantined(self, index: int) -> bool:
        return index in self._quarantined

    def quarantined_indexes(self) -> List[int]:
        return sorted(self._quarantined)

    def reputation(self, index: int) -> float:
        return self._rep.get(index, 0.0)

    def tick(self) -> List[int]:
        """One probation period elapsed (a round close in sync modes, a
        publish in async). Returns the indexes released this tick. The
        period a rank was quarantined IN does not count — a rank sits
        out exactly ``quarantine_rounds`` full cohorts/publishes after
        the one that tripped it."""
        released = []
        for idx in list(self._quarantined):
            if idx in self._fresh:
                self._fresh.discard(idx)
                continue
            self._quarantined[idx] -= 1
            if self._quarantined[idx] <= 0:
                del self._quarantined[idx]
                released.append(idx)
        if released:
            logging.info(
                "defense: probation expired for rank index(es) %s — "
                "re-eligible with a fresh reputation", released,
            )
        return released
