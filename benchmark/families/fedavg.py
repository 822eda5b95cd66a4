"""Family driver: FedAvg simulation through the program's normal path.

Drives ``fedml_tpu.init`` -> ``data.load`` -> ``models.create`` ->
``FedAvgAPI(...).train()``, i.e. ``core/round_pipeline.RoundPipeline.run``:
cohort sampling, bucket padding, the round executable with local
training and the in-jit weighted aggregation, ``_eval_all`` at the
cell's cadence, the deferred metric flush. Never an executable by hand.

What the benchmark makes itself, from ``--seed``: the weights (the
reference's ``init_params``, handed to the program as its
``global_params``) and the images (class means plus noise, on the
device). The program's loader makes the *partition*: client sizes, the
shared ``num_batches`` and the masks come from ``data.load`` with the
configuration's fixed ``partition_seed``, so every seed runs the same
shapes and the same useful samples a round, and the program's packing
policy stays under test.

``train()`` takes a round count, not a duration. Set-up warms up with
one ``train()`` of the window's own length -- the check's three rounds
alone would leave the host's per-horizon programs (the RNG chain's scan
and the slices of its keys, shaped by the round count) to the window's
first call, which then ran 0.73 s longer than its second on the chip --
then drives the check's rounds through the same object. The window is
filled with ``train()`` calls of ``rounds_per_call`` rounds, and the
rate is all useful samples over all of the window's wall time, the
drains at each call's end included.
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Dict

import numpy as np

import harness
from harness import BenchError


def _leaf_norms_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def leaf_norms(after, before):
        return jnp.stack([
            jnp.sqrt(jnp.sum((a.astype(jnp.float32) - b.astype(jnp.float32)) ** 2))
            for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(before))
        ])

    return leaf_norms


def synth_images(seed: int, y, shape, classes: int, dtype):
    """Stand-in images on the device from the seed: a fixed mean image
    per class plus unit noise, rounded to bfloat16 and held in the type
    the program trains on -- so a program built in float32 (the witness
    of ``tools/calibrate.py``) sees the very values the configured one
    does. Rows all differ. ``y`` is the packed label array [..., bs]."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key, y):
        means = jax.random.normal(jax.random.fold_in(key, 1), (classes,) + tuple(shape), jnp.float32)
        noise = jax.random.normal(jax.random.fold_in(key, 2), y.shape + tuple(shape), jnp.bfloat16)
        return (means[y] + noise.astype(jnp.float32)).astype(jnp.bfloat16).astype(dtype)

    return make(jax.random.PRNGKey(seed % (2 ** 31)), y)


class Driver:
    def __init__(self, cell: harness.Cell, seed: int) -> None:
        self.cell, self.seed = cell, int(seed)
        self.cfg, self.wl = cell.config, cell.traffic
        self.model, self.fed = self.cfg["model"], self.cfg["federation"]
        self.ref = cell.module("reference", self.cfg["reference"])
        self.spans: Dict[str, float] = {}
        self.observed: Dict[str, Any] = {}
        self.api = None

    # -- set-up --------------------------------------------------------
    def _args(self):
        from fedml_tpu.arguments import Arguments

        flat = dict(self.cfg["program_args"])
        flat.update(self.wl.get("program_args", {}))
        flat["random_seed"] = int(self.fed["partition_seed"])
        return Arguments(argparse.Namespace(**flat), training_type="simulation")

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        import fedml_tpu
        from fedml_tpu import data, models

        t0 = time.perf_counter()
        args = self.args = fedml_tpu.init(self._args())
        ds = data.load(args)
        jax.block_until_ready(ds.packed_train.x)
        self.spans["data_setup_s"] = time.perf_counter() - t0

        # the benchmark's own images over the program's packing
        t0 = time.perf_counter()
        dtype = ds.packed_train.x.dtype
        shape, classes = tuple(self.model["image"]), int(self.model["classes"])
        if tuple(ds.packed_train.x.shape[-3:]) != shape or ds.class_num != classes:
            raise BenchError(
                f"program data {ds.packed_train.x.shape} / {ds.class_num} classes "
                f"is not the configuration's {shape} / {classes}")
        train = ds.packed_train.replace(
            x=synth_images(self.seed, ds.packed_train.y, shape, classes, dtype))
        test = ds.packed_test.replace(
            x=synth_images(self.seed + 1, ds.packed_test.y, shape, classes, dtype))
        ds = dataclasses.replace(
            ds, packed_train=train, packed_test=test, train_data_global=None,
            test_data_global=None, train_data_local_dict={}, test_data_local_dict={})
        self.nsamples = np.asarray(ds.packed_num_samples, np.float64)
        self.packed = {
            "train": (train.x, train.y, train.mask),
            "test": (test.x, test.y, test.mask),
        }
        self.train_samples = float(jnp.sum(train.mask))
        self.test_samples = float(jnp.sum(test.mask))
        self.spans["bench_synth_s"] = time.perf_counter() - t0

        from fedml_tpu.simulation.fedavg_api import FedAvgAPI

        api = self.api = FedAvgAPI(args, None, ds, models.create(args, ds.class_num))

        # the benchmark's weights in the program's place
        self.w0 = self.ref.init_params(self.seed, self.model)
        want = jax.tree.map(lambda a: (a.shape, str(a.dtype)), self.w0)
        have = jax.tree.map(lambda a: (a.shape, str(a.dtype)), api.global_params)
        if want != have:
            raise BenchError(
                "the program's parameter tree is not the configuration's: "
                f"{jax.tree.structure(have)} vs {jax.tree.structure(want)}")
        self._copy = jax.jit(lambda t: jax.tree.map(jnp.copy, t))
        self._norms = _leaf_norms_fn()

        # warm-up: one call of the window's own kind (every executable
        # and every host-side program shaped by the round count)
        t0 = time.perf_counter()
        self._set_call(self.wl["rounds_per_call"], self.wl["eval_every"])
        api.train()
        self.spans["warmup_s"] = time.perf_counter() - t0

        # the check's drive: the same object, from the seed's weights,
        # through train() -- one round, then two more, an evaluation
        # after each
        t0 = time.perf_counter()
        api.history.clear()
        api.global_params = self._copy(self.w0)
        self._set_call(1, 1)
        api.train()
        w1 = self._copy(api.global_params)
        self._set_call(2, 1)
        api.train()
        hist = list(api.history)
        if len(hist) != 3:
            raise BenchError(f"the check's three rounds left {len(hist)} records")
        self.observed = {
            "loss": [float(h["train_loss_cohort"]) for h in hist],
            "eval_train": [float(hist[0]["train_loss"])],
            "eval_test": [float(h["test_loss"]) for h in hist],
            "first_norms": np.asarray(self._norms(w1, self.w0)),
            "change_norms": np.asarray(self._norms(api.global_params, self.w0)),
            "packed_train_samples": self.train_samples,
        }
        del w1
        self.spans["check_drive_s"] = time.perf_counter() - t0
        self._set_call(self.wl["rounds_per_call"], self.wl["eval_every"])

    def _set_call(self, rounds: int, eval_every: int) -> None:
        self.args.comm_round = int(rounds)
        self.args.frequency_of_the_test = int(eval_every)

    # -- the window ----------------------------------------------------
    def window(self, seconds: float) -> Dict[str, Any]:
        import jax

        api, rec = self.api, self.api.telemetry.recorder
        rounds_per_call = int(self.wl["rounds_per_call"])
        per_round = int(self.args.client_num_per_round)
        clients = int(self.fed["clients"])
        useful_per_call = sum(
            float(self.nsamples[self.ref.sample_cohort(r, clients, per_round)].sum())
            for r in range(rounds_per_call))
        n_hist = len(api.history)
        rec.instant("bench.window_start", cat="bench")
        t0 = time.perf_counter()
        call_ends = []
        while time.perf_counter() - t0 < seconds:
            api.train()
            call_ends.append(time.perf_counter() - t0)
        jax.block_until_ready(api.global_params)
        wall = time.perf_counter() - t0
        calls = len(call_ends)
        rec.instant("bench.window_end", cat="bench")

        events = rec.tail(rec.capacity)
        marks = [e for e in events if e["name"] in ("bench.window_start", "bench.window_end")]
        lo, hi = marks[-2]["ts"], marks[-1]["ts"]
        done = [e["ts"] for e in events
                if e["name"] == "pipeline.dispatch" and lo <= e["ts"] <= hi]
        rounds = calls * rounds_per_call
        if len(done) != rounds:
            raise BenchError(f"{rounds} rounds ran but {len(done)} completions were recorded")
        edges = [lo] + done
        intervals_ms = [(b - a) / 1e3 for a, b in zip(edges, edges[1:])]
        hist = api.history[n_hist:]
        bad = sum(
            1 for h in hist
            if not all(np.isfinite(h[k]) for k in ("train_loss_cohort", "train_loss", "test_loss")))
        packed = api.dataset.packed_train
        bucket = int(api.pipeline_stats.get("bucket", per_round))
        slots_per_round = bucket * int(packed.mask.shape[1]) * int(packed.mask.shape[2])
        return {
            "wall_s": wall,
            "units": rounds,
            "failed": bad,
            "rounds": rounds,
            "calls": calls,
            "call_s": [b - a for a, b in zip([0.0] + call_ends, call_ends)],
            "evals": len(hist),
            "useful_samples": useful_per_call * calls,
            "slot_samples": float(slots_per_round * rounds),
            "eval_samples": (self.train_samples + self.test_samples) * len(hist),
            "round_intervals_ms": intervals_ms,
            "host_syncs_per_round": float(api.pipeline_stats.get("host_syncs_per_round", 0.0)),
            "bucket": bucket,
        }

    def end_to_end(self, win: Dict[str, Any]) -> Dict[str, float]:
        return {
            "samples_per_s": win["useful_samples"] / win["wall_s"],
            "round_p95_ms": harness.percentile_nearest_rank(win["round_intervals_ms"], 0.95),
        }

    # -- after the window ----------------------------------------------
    def release(self) -> None:
        """Free what the program holds on the device; keep the inputs
        and the seed's weights for the reference."""
        import jax

        self.api.global_params = None
        self.api.dataset = None
        self.api = None
        jax.clear_caches()

    def reference_numbers(self, quant=None, row_keep: int = 0) -> Dict[str, Any]:
        """The plain reference over the check's three rounds and the
        evaluations it follows (the held-out split after each round,
        the training split after the first)."""
        import jax

        ref, model, fed = self.ref, self.model, self.fed
        clients, per_round = int(fed["clients"]), int(self.args.client_num_per_round)
        norms = _leaf_norms_fn()
        out = {"loss": [], "eval_test": [], "eval_train": []}
        w = self.w0
        with jax.default_matmul_precision("highest"):
            for i, r in enumerate((0, 0, 1)):
                cohort = ref.sample_cohort(r, clients, per_round)
                w, loss = ref.fedavg_round(
                    w, self.packed["train"], self.nsamples, cohort, model, fed,
                    quant=quant, row_keep=row_keep)
                out["loss"].append(loss)
                out["eval_test"].append(ref.evaluate(w, self.packed["test"], model, quant))
                if i == 0:
                    out["eval_train"].append(ref.evaluate(w, self.packed["train"], model, quant))
                    out["first_norms"] = np.asarray(norms(w, self.w0))
            out["change_norms"] = np.asarray(norms(w, self.w0))
        out["packed_train_samples"] = float(fed["train_samples"])
        return out

    def gaps(self, got: Dict[str, Any], want: Dict[str, Any]) -> Dict[str, float]:
        return gaps(got, want)

    def compare(self, compared: harness.Compared) -> None:
        limits = self.cfg["limits"]
        g = self.gaps(self.observed, self.reference_numbers())
        for name in ("loss_gap", "eval_gap", "first_norm_gap", "change_norm_gap", "packed_samples_gap"):
            compared.add(name, g[name], float(limits[name]))

    # -- facts the per-layer readers use -------------------------------
    def facts(self) -> Dict[str, Any]:
        from fedml_tpu.core import compile_cache

        return {
            "spans": dict(self.spans),
            "counters": {"compile_cache_misses": float(compile_cache.stats()["misses"])},
        }


def gaps(got: Dict[str, Any], want: Dict[str, Any]) -> Dict[str, float]:
    """The five numbers a FedAvg cell compares."""
    return {
        "loss_gap": max(harness.rel_gap(a, b) for a, b in zip(got["loss"], want["loss"])),
        "eval_gap": max(
            harness.rel_gap(a, b)
            for k in ("eval_train", "eval_test")
            for a, b in zip(got[k], want[k])),
        "first_norm_gap": harness.worst_leaf_gap(got["first_norms"], want["first_norms"]),
        "change_norm_gap": harness.worst_leaf_gap(got["change_norms"], want["change_norms"]),
        "packed_samples_gap": abs(got["packed_train_samples"] - want["packed_train_samples"]),
    }
