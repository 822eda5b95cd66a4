"""Async round-pipeline executor: K federation rounds in flight.

The jitted round engine (``simulation/fedavg_api.py``) already makes one
round a single XLA computation, but the driver loop around it was
synchronous: every iteration materialized host floats
(``float(summed["loss_sum"])``), split RNGs one step at a time, and ran
eval fetches inline — each a device round-trip that stalls XLA's async
dispatch queue. PiPar (arXiv:2302.12803) and FedML Parrot
(arXiv:2303.01778) both locate simulator throughput in exactly this
idle time; this executor removes it:

- **Horizon precompute.** Client sampling is host-deterministic by
  ``round_idx`` (``deterministic_client_sampling``), the round-RNG
  chain is a pure split sequence, and the round-LR multiplier is host
  math — so cohort indices, per-round RNG keys, and LR multipliers for
  the whole remaining horizon are computed before the first dispatch.
- **K rounds in flight.** Round computations are dispatched
  back-to-back; global params / server-opt state are donated buffers
  chained on device, so XLA serializes the math while the host runs
  ahead. A depth-K token queue applies back-pressure with
  ``block_until_ready`` (a wait, not a transfer) so at most K rounds of
  work are queued.
- **Deferred metrics.** Eval rounds dispatch the eval computations and
  push the device scalars into a ``DeferredMetrics`` ring
  (``core/tracking.py``); records are flushed — ONE device fetch for
  everything pending — every ``frequency_of_the_test`` rounds (only
  records at least K-1 rounds old, so the fetch never stalls on
  in-flight compute) or at pipeline drain (checkpoint save / end of
  run). Between flushes the hot loop performs **zero** device fetches.
- **Shape-bucketed compile cache.** Cohort sizes are padded up to
  power-of-two buckets: the padded slots reuse a real client index but
  get an all-zero validity mask (their batches mask out, their weight
  is zero — the same invisibility argument as ``parallel/mesh.py``'s
  ``pad_federation``), so the 8→512 scaling sweep and mid-run cohort
  changes hit the jit cache instead of retracing. Aggregators that are
  not weight-aware (coordinate median, custom server aggregators) fall
  back to exact-size cohorts automatically.

``pipeline_depth: 1`` (the default) recovers synchronous behavior with
identical metrics — K=1 flushes every record at its own eval round.
"""

from __future__ import annotations

import logging
import time
from collections import deque
from typing import Any, Dict, Optional

import numpy as np

# bucket/pad helpers live in core/bucketing.py (shared with the serving
# plane's micro-batcher); re-exported here for compat — both names were
# part of this module's public surface before the factor-out
from .bucketing import bucket_cohort, pad_cohort_idx  # noqa: F401
from .devtime import measure as _devtime
from .local_trainer import LANE_STEPS, lane_steps, model_counters
from .tracking import DeferredMetrics

__all__ = ["RoundPipeline", "bucket_cohort", "pad_cohort_idx"]


def _rng_chain(rng, n: int):
    """``n`` steps of ``rng, k = split(rng)`` as one jitted scan:
    returns ``(keys[n, ...], heads[n, ...])`` where ``keys[i]`` is
    round i's key and ``heads[i]`` the chain head after its split —
    value-identical to the synchronous loop's python chain."""
    import jax

    def step(carry, _):
        nxt, k = jax.random.split(carry)
        return nxt, (k, nxt)

    _, (keys, heads) = jax.lax.scan(step, rng, None, length=n)
    return keys, heads


class RoundPipeline:
    """Drives an eligible FedAvg-family API's round loop with K rounds
    in flight. Constructed per ``train()`` call; owns the horizon
    precompute, the in-flight token queue, the deferred-metrics ring,
    and the drain points (checkpoint / end of run).

    ``stats`` after ``run``: rounds executed, flushes, host syncs, and
    ``host_syncs_per_round`` — the figure the benchmark's round-pipeline
    layer reports and tests/test_round_pipeline.py bounds.
    """

    def __init__(self, api, depth: Optional[int] = None) -> None:
        self.api = api
        args = api.args
        self.depth = max(1, int(depth if depth is not None
                                else getattr(args, "pipeline_depth", 1)))
        self.bucket_policy = str(getattr(args, "pipeline_bucket", "pow2"))
        # weight-unaware reductions cannot absorb zero-weight padding:
        # coordinate median ignores weights entirely, and a custom
        # server aggregator's semantics are unknown — exact cohorts
        if (
            getattr(api, "server_aggregator", None) is not None
            or getattr(args, "defense_type", None) == "median"
        ):
            self.bucket_policy = "exact"
        self.deferred = DeferredMetrics()
        self.stats: Dict[str, Any] = {}
        self._extra_syncs = 0  # non-metric fetches (drains count wall time only)

    # -- horizon precompute -------------------------------------------
    def precompute(self, start_round: int, comm_rounds: int):
        """Indices / RNG chain / LR multipliers for [start, comm_rounds).
        Reads the API and changes nothing in it, so a caller may plan a
        horizon ahead of ``train()`` to have its host-side programs (the
        chain's scan and the slices of its keys, shaped by the round
        count) built before the first call of that length.

        The RNG chain reproduces the synchronous loop's per-round
        ``self.rng, k = split(self.rng)`` sequence exactly — generated
        as ONE jitted scan (a single device dispatch for the whole
        horizon, not one per round), so K=1/K=4 and checkpoint-resumed
        runs all see identical draws."""
        import jax

        api = self.api
        args = api.args
        rounds = range(start_round, comm_rounds)
        idx_plan = [
            api._client_sampling(
                r, api.dataset.client_num, int(args.client_num_per_round)
            )
            for r in rounds
        ]
        lr_plan = [api._lr_mult(r) for r in rounds]
        n = len(idx_plan)
        if n == 0:
            return idx_plan, lr_plan, [], []
        keys_arr, heads_arr = _rng_chain(api.rng, n)
        if api._multi_controller:
            # one fetch for the whole chain — process-consistent host
            # values, outside the hot loop
            keys_arr = np.asarray(keys_arr)  # lint: host-sync-ok
            heads_arr = np.asarray(heads_arr)  # lint: host-sync-ok — one pre-loop fetch (comment above)
        keys = [keys_arr[i] for i in range(n)]
        heads = [heads_arr[i] for i in range(n)]
        return idx_plan, lr_plan, keys, heads

    # -- run ----------------------------------------------------------
    def run(
        self, packed, nsamples, comm_rounds: int, freq: int, ckpt, start_round: int
    ) -> Dict[str, float]:
        import jax
        import jax.numpy as jnp

        api = self.api
        args = api.args
        # host phase spans (docs/observability.md has the table): every
        # `span` below is a TraceAnnotation on the device trace's clock
        # and a B/E pair in the flight recorder; `train.plan`, the
        # `round`s and `train.drain` tile this call, and a round's
        # children tile the round
        span = api.profiler.span
        with span("train.plan"):
            n_per_round = int(args.client_num_per_round)
            # compile buckets must tile the mesh's cohort axis ('clients'
            # legacy / 'data' on the fed (data, fsdp) mesh) so every padded
            # cohort shards evenly across the lanes
            from ..parallel.layout import cohort_axis_size

            shard_multiple = cohort_axis_size(api.mesh)
            bucket = bucket_cohort(
                n_per_round,
                self.bucket_policy,
                max_size=int(api.dataset.client_num),  # lint: host-sync-ok — host metadata
                shard_multiple=shard_multiple,
            )
            idx_plan, lr_plan, key_plan, head_plan = self.precompute(
                start_round, comm_rounds
            )
            # what an evaluation reads: the packed splits less the
            # clients' padding (made by train() before this call)
            eval_train, eval_test = api._eval_splits()

        # telemetry (core/telemetry.py): every instrument below is a
        # host-side counter bump / ring append — the hot loop gains no
        # device fetches, so host_syncs_per_round is bit-identical with
        # telemetry on or off (tests/test_telemetry.py asserts this)
        tel = getattr(api, "telemetry", None)
        tel = tel if tel is not None and tel.enabled else None
        rec = tel.recorder if tel is not None else None
        if tel is not None:
            tel.attach_deferred(self.deferred)

        inflight: deque = deque()
        final_stats: Dict[str, float] = {}
        # every round's (steps_run, steps_packed): device scalars until
        # an evaluation round's record takes them to the host with it,
        # where they add up over the call
        steps_pending: list = []
        steps_total = np.zeros(2)
        # per-round wall durations: dispatch-to-next-dispatch, finalized
        # when the following round dispatches (a deferred record may be
        # flushed K-1 rounds after its round; "now - t0" there would
        # charge the round for the whole pipeline lag)
        t_dispatch: Dict[int, float] = {}
        durations: Dict[int, float] = {}
        prev_round: Optional[int] = None
        ckpt_freq = getattr(api, "_ckpt_freq", 1)

        def flush(upto: Optional[int]) -> None:
            nonlocal final_stats, steps_total
            if not len(self.deferred):
                return
            with span("flush.fetch"):
                flushed = self.deferred.flush(upto)
            if not flushed:
                return
            if rec is not None:
                rec.instant(
                    "pipeline.flush" if upto is not None else "pipeline.drain",
                    cat="pipeline",
                    records=len(flushed),
                    upto=upto,
                )
            with span("flush.report"):
                for r, host in flushed:
                    t0r = t_dispatch.pop(r, None)
                    dt = durations.pop(r, None)
                    if dt is None and t0r is not None:
                        # only possible for the just-dispatched round
                        # (K=1's same-iteration flush): legacy
                        # semantics, round start to now
                        dt = time.perf_counter() - t0r
                    steps_total += np.sum(host["lane_steps"], axis=0)
                    stats = self._stats_from_host(r, host, dt)
                    api.history.append(stats)
                    final_stats = stats
                    api.metrics_reporter.report_server_training_metric(stats)

        # on-demand device profiling (core/tracing.py): with K rounds in
        # flight the capture window is dispatch-to-dispatch of the listed
        # round, which brackets its device work under back-pressure
        profiler = getattr(api, "_round_profiler", None)

        # the body stays in this frame: as a nested function of its own
        # it made every trace taken from inside it a quarter slower on
        # the v5e's host (+2.9 s of set-up; PERF.md §6, PR 26)
        for i, round_idx in enumerate(range(start_round, comm_rounds)):
            with api.profiler.iteration_span("round", round=round_idx):
                if profiler is not None:
                    profiler.tick(round_idx)
                with span("round.prep"):
                    t0 = time.perf_counter()
                    if prev_round is not None and prev_round in t_dispatch:
                        durations[prev_round] = t0 - t_dispatch[prev_round]
                    prev_round = None
                    pidx, valid = pad_cohort_idx(idx_plan[i], bucket)
                    if api._multi_controller:
                        idx_dev, valid_dev = pidx, valid
                    else:
                        idx_dev, valid_dev = jnp.asarray(pidx), jnp.asarray(valid)
                    lr_mult = lr_plan[i]
                    extra = () if lr_mult is None else (lr_mult,)
                with span("round.dispatch"):
                    with _devtime(api._round_exec_name(), bucket=f"b{bucket}"):
                        out = api._round_fn(
                            api.global_params,
                            api.server_state,
                            packed,
                            nsamples,
                            idx_dev,
                            key_plan[i],
                            *extra,
                            valid=valid_dev,
                        )
                api.global_params, api.server_state, summed = out[:3]
                api.rng = head_plan[i]
                # back-pressure: bound in-flight rounds at K with a wait
                # (block_until_ready), never a transfer — after the wait at
                # most K-1 unconfirmed rounds remain, so the next dispatch
                # brings the queue back to exactly K (depth=1: wait on the
                # round just dispatched, i.e. fully synchronous)
                inflight.append(summed["count"])
                steps_pending.append([summed[k] for k in LANE_STEPS])
                with span("round.wait"):
                    while len(inflight) >= self.depth:
                        jax.block_until_ready(inflight.popleft())  # lint: host-sync-ok — THE back-pressure sync (depth bound)
                if tel is not None:
                    tel.inc("pipeline_rounds_dispatched_total")
                    tel.heartbeat("pipeline.round", round_idx)
                    rec.instant("pipeline.dispatch", cat="pipeline", round=round_idx)

                if round_idx % freq == 0 or round_idx == comm_rounds - 1:
                    with span("eval"):
                        train_sums = api._eval_all(api.global_params, eval_train)
                        test_sums = api._eval_all(api.global_params, eval_test)
                        t_dispatch[round_idx] = t0
                        prev_round = round_idx
                        self.deferred.push(
                            round_idx,
                            {"summed": summed, "train": train_sums, "test": test_sums,
                             "lane_steps": steps_pending},
                        )
                        steps_pending = []
                    # flush every eval round, but only records at least
                    # K-1 rounds old — the fetch never waits on in-flight
                    # compute (K=1: flush this round's record immediately,
                    # i.e. exactly the synchronous loop's behavior)
                    flush(round_idx - (self.depth - 1))

                saved = False
                if ckpt is not None and (
                    (round_idx + 1) % ckpt_freq == 0 or round_idx == comm_rounds - 1
                ):
                    with span("round.ckpt"):
                        # drain before save: all pending metrics out, then the
                        # checkpoint fetches params (inherently a host sync)
                        flush(None)
                        api._save_checkpoint(ckpt, round_idx)
                    self._extra_syncs += 1
                    saved = True
                signal = getattr(api, "_preempt_signal", None)
                if signal is not None:
                    notice = signal.poll(round_idx)
                    if notice is not None:
                        # drain the depth-K window DETERMINISTICALLY before
                        # the forced snapshot: every in-flight round's
                        # confirmation waited on (same barrier as the depth
                        # bound), deferred metrics out — the checkpoint then
                        # holds exactly the rounds the WAL says it does
                        while inflight:
                            jax.block_until_ready(inflight.popleft())  # lint: host-sync-ok — preempt drain (same barrier as the depth bound)
                        flush(None)
                        self._extra_syncs += 1
                        from ..parallel.elastic import preempt_now

                        preempt_now(api, ckpt, round_idx, notice, saved=saved)

        with span("train.drain"):
            flush(None)
        n_rounds = max(1, comm_rounds - start_round)
        self.stats = {
            "depth": self.depth,
            "bucket": bucket,
            "bucket_policy": self.bucket_policy,
            "rounds": comm_rounds - start_round,
            "flushes": self.deferred.flushes,
            "host_syncs": self.deferred.host_syncs + self._extra_syncs,
            "host_syncs_per_round": round(
                (self.deferred.host_syncs + self._extra_syncs) / n_rounds, 4
            ),
        }
        # lane-steps the cohort's step loops ran over those its lanes
        # were packed to, all rounds of this call (1.0: nothing skipped)
        if steps_total[1]:
            share = steps_total[0] / steps_total[1]  # host numbers both
            self.stats["lane_steps_run_share"] = share
            if tel is not None:
                tel.set_gauge("pipeline_lane_steps_run_share", share)
        api.pipeline_stats = self.stats
        if tel is not None:
            tel.set_gauge("pipeline_depth", self.depth)
            tel.set_gauge("pipeline_bucket", bucket)
            tel.set_gauge(
                "pipeline_host_syncs_per_round",
                self.stats["host_syncs_per_round"],
            )
        logging.debug("round pipeline: %s", self.stats)
        return final_stats

    # -- host-side metric assembly (post-fetch, no device access) -----
    def _stats_from_host(
        self, round_idx: int, host: Dict[str, Any], duration_s: Optional[float]
    ) -> Dict[str, float]:
        api = self.api
        tr = api.model.metrics_from_sums(host["train"])
        te = api.model.metrics_from_sums(host["test"])
        summed = host["summed"]
        stats = {
            "train_acc": tr["acc"],
            "train_loss": tr["loss"],
            "test_acc": te["acc"],
            "test_loss": te["loss"],
            "round": round_idx,
            "round_time_s": duration_s if duration_s is not None else 0.0,
            # eval-round flush: metrics leave the device here by
            # design (DeferredMetrics already drained)
            "train_loss_cohort": float(summed["loss_sum"])  # lint: host-sync-ok
            / max(float(summed["count"]), 1.0),  # lint: host-sync-ok
        }
        stats.update(model_counters(summed))
        stats.update(lane_steps(summed))
        return stats
