"""Family driver: FedAvg simulation of a language model, through the
program's normal path.

``families/fedavg.py``'s driver (``fedml_tpu.init`` -> ``data.load`` ->
``models.create`` -> ``FedAvgAPI(...).train()``, the same check's
drive, window and comparison) with tokens where that one has images: a
sample is one packed sequence of ``model.seq_len`` token ids and its
next tokens. Two things differ, both because a run of this family
costs minutes: the drive is the warm-up (``setup``), and the numbers
compared are the ones the configuration's ``limits`` name (``gaps``). What the benchmark makes itself, from ``--seed``:
the weights (the reference's ``init_params``) and the tokens (a chain
over the configuration's vocabulary slice, on the device). The
program's loader makes the *partition*: which silo holds how many
sequences, the shared ``num_batches`` and the masks come from
``data.load`` with the configuration's fixed ``partition_seed``.

Besides what ``fedavg.py``'s window returns, this one counts the
sequence slots an evaluation computes (``eval_slot_samples``: a silo's
held-out batch may be partly mask) and sums the expert layer's counters
over the window's reported rounds (``counters``), for the readers that
``BENCHMARK.json`` lists for this family's cells alone.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time
from typing import Any, Dict

import numpy as np

import fedavg as images
import harness
from harness import BenchError

COUNTERS = ("moe_local_hits", "moe_expert_tokens_max", "moe_expert_tokens_mean", "moe_dropped")


def synth_tokens(seed: int, shape, vocab: int):
    """Stand-in token streams on the device from the seed: ``shape +
    (T + 1,)`` ids below ``vocab``. A token is followed by its fixed
    successor (a seeded permutation of the vocabulary) with probability
    1/2 and by a uniform draw otherwise: half of the next tokens can be
    learned. Returns ``(x, y)`` = (tokens[..., :-1], tokens[..., 1:])."""
    import jax
    import jax.numpy as jnp

    *lead, length = shape

    @jax.jit
    def make(key):
        succ = jax.random.permutation(jax.random.fold_in(key, 1), vocab)
        first = jax.random.randint(jax.random.fold_in(key, 2), tuple(lead), 0, vocab)
        draws = jax.random.randint(jax.random.fold_in(key, 3), (length,) + tuple(lead), 0, vocab)
        follow = jax.random.bernoulli(jax.random.fold_in(key, 4), 0.5, (length,) + tuple(lead))

        def step(cur, inputs):
            draw, keep = inputs
            nxt = jnp.where(keep, succ[cur], draw)
            return nxt, nxt

        _, rest = jax.lax.scan(step, first, (draws, follow))
        toks = jnp.concatenate([first[None], rest], axis=0)  # [T + 1, ...]
        toks = jnp.moveaxis(toks, 0, -1).astype(jnp.int32)
        return toks[..., :-1], toks[..., 1:]

    return make(jax.random.PRNGKey(seed % (2 ** 31)))


class Driver(images.Driver):
    # -- set-up --------------------------------------------------------
    def load_data(self):
        """The program's arguments and partition, the benchmark's tokens
        over it and the seed's weights: everything the reference needs,
        before any of the program's executables exists
        (``tools/calibrate_lm.py`` stops here)."""
        import jax
        import jax.numpy as jnp

        import fedml_tpu
        from fedml_tpu import data

        t0 = time.perf_counter()
        args = self.args = fedml_tpu.init(self._args())
        ds = data.load(args)
        jax.block_until_ready(ds.packed_train.x)
        self.spans["data_setup_s"] = time.perf_counter() - t0

        # the benchmark's own tokens over the program's packing
        t0 = time.perf_counter()
        seq, vocab = int(self.model["seq_len"]), int(self.model["vocab_size"])
        if tuple(ds.packed_train.x.shape[-1:]) != (seq,) or ds.class_num != vocab:
            raise BenchError(
                f"program data {ds.packed_train.x.shape} / vocabulary {ds.class_num} "
                f"is not the configuration's sequences of {seq} over {vocab}")
        parts = {}
        for name, packed, seed in (("train", ds.packed_train, self.seed),
                                   ("test", ds.packed_test, self.seed + 1)):
            x, y = synth_tokens(seed, packed.x.shape, vocab)
            parts[name] = packed.replace(x=x.astype(packed.x.dtype), y=y.astype(packed.y.dtype))
        ds = dataclasses.replace(
            ds, packed_train=parts["train"], packed_test=parts["test"], train_data_global=None,
            test_data_global=None, train_data_local_dict={}, test_data_local_dict={})
        self.nsamples = np.asarray(ds.packed_num_samples, np.float64)
        self.packed = {k: (p.x, p.y, p.mask) for k, p in parts.items()}
        self.train_samples = float(jnp.sum(parts["train"].mask))
        self.test_samples = float(jnp.sum(parts["test"].mask))
        self.eval_slots = float(parts["train"].mask.size + parts["test"].mask.size)
        # the seed's weights, kept on the host: beside the round
        # executable's 13.5 GB the chip has no room for a spare copy
        self.w0 = jax.device_get(self.ref.init_params(self.seed, self.model))
        self._norms = images._leaf_norms_fn()
        self.spans["bench_synth_s"] = time.perf_counter() - t0
        return ds

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from fedml_tpu import models

        ds, args = self.load_data(), self.args
        from fedml_tpu.simulation.fedavg_api import FedAvgAPI

        api = self.api = FedAvgAPI(args, None, ds, models.create(args, ds.class_num))

        # the benchmark's weights in the program's place
        want = jax.tree.map(lambda a: (a.shape, str(a.dtype)), self.w0)
        have = jax.tree.map(lambda a: (a.shape, str(a.dtype)), api.global_params)
        if want != have:
            raise BenchError(
                "the program's parameter tree is not the configuration's: "
                f"{jax.tree.structure(have)} vs {jax.tree.structure(want)}")
        self._copy = jax.jit(lambda t: jax.tree.map(jnp.copy, t))

        # warm-up. The check's drive below runs both executables (the
        # round's and the evaluation's, the shapes the window runs) from
        # the seed's weights, so it warms them; a call of the window's
        # own length, as the ResNet cells warm up with, would add 60 s
        # of rounds to every run. What such a call builds besides is
        # the host's per-horizon programs (the RNG chain's scan over a
        # call's rounds and the slices of its keys): the round
        # pipeline's plan for that horizon builds them and runs no round
        t0 = time.perf_counter()
        from fedml_tpu.core.round_pipeline import RoundPipeline

        jax.block_until_ready(
            RoundPipeline(api).precompute(0, int(self.wl["rounds_per_call"]))[2:])
        self.spans["warmup_s"] = time.perf_counter() - t0

        # the check's drive: the same object, from the seed's weights,
        # through train() -- one round, then two more, an evaluation
        # after each
        t0 = time.perf_counter()
        api.history.clear()
        api.global_params = self._copy(self.w0)
        self._set_call(1, 1)
        api.train()
        w1 = jax.device_get(api.global_params)  # on the host: see w0
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
            "counters": [{k: float(h[k]) for k in COUNTERS if k in h} for h in hist],
        }
        del w1
        self.spans["check_drive_s"] = time.perf_counter() - t0
        # the window trains on from the seed's weights, as a fine-tune
        # would: not from wherever the check's rounds have led
        api.global_params = self._copy(self.w0)
        self._set_call(self.wl["rounds_per_call"], self.wl["eval_every"])

    # -- the window ----------------------------------------------------
    def window(self, seconds: float) -> Dict[str, Any]:
        api, n_hist = self.api, len(self.api.history)
        win = super().window(seconds)
        hist = api.history[n_hist:]
        win["eval_slot_samples"] = self.eval_slots * len(hist)
        win["counters"] = {
            k: float(sum(h[k] for h in hist)) for k in COUNTERS if all(k in h for h in hist) and hist}
        if win["counters"].get("moe_dropped", 0.0) > 0:
            win["failed"] += len(hist)  # a dropped token-choice is a failed round
        return win

    def facts(self) -> Dict[str, Any]:
        import jax

        # everything the device's allocator reports, on the stderr of a
        # run: ``memory_peak_bytes`` (its ``peak_bytes_in_use``) holds
        # none of the round executable's temporaries (PERF.md 7 (c))
        print("memory_stats " + json.dumps(jax.devices()[0].memory_stats() or {}),
              file=sys.stderr, flush=True)
        return super().facts()

    # -- after the window ----------------------------------------------
    def reference_numbers(self, quant=None, row_keep: int = 0, fault=None) -> Dict[str, Any]:
        """The plain reference over the check's three rounds and, where
        the configuration limits ``eval_gap``, the evaluations it
        follows; ``fault`` plants one of the reference's named faults
        (limit readings only)."""
        import jax

        evaluate = "eval_gap" in self.cfg["limits"]
        ref, model, fed = self.ref, self.model, self.fed
        clients, per_round = int(fed["clients"]), int(self.args.client_num_per_round)
        norms = images._leaf_norms_fn()
        out = {"loss": [], "eval_test": [], "eval_train": []}
        w = self.w0
        with jax.default_matmul_precision("highest"):
            for i, r in enumerate((0, 0, 1)):
                cohort = ref.sample_cohort(r, clients, per_round)
                w, loss = ref.fedavg_round(
                    w, self.packed["train"], self.nsamples, cohort, model, fed,
                    quant=quant, row_keep=row_keep, fault=fault)
                out["loss"].append(loss)
                if evaluate:
                    out["eval_test"].append(
                        ref.evaluate(w, self.packed["test"], model, quant, fault))
                if i == 0:
                    if evaluate:
                        out["eval_train"].append(
                            ref.evaluate(w, self.packed["train"], model, quant, fault))
                    out["first_norms"] = np.asarray(norms(w, self.w0))
            out["change_norms"] = np.asarray(norms(w, self.w0))
        out["packed_train_samples"] = float(fed["train_samples"])
        return out

    def gaps(self, got: Dict[str, Any], want: Dict[str, Any]) -> Dict[str, float]:
        return gaps(got, want)

    def compare(self, compared: harness.Compared) -> None:
        limits, want = self.cfg["limits"], self.reference_numbers()
        # the three rounds' losses side by side: they have to fall
        print("check_loss " + json.dumps(
            {"program": self.observed["loss"], "reference": want["loss"]}), file=sys.stderr, flush=True)
        g = self.gaps(self.observed, want)
        unread = [name for name in limits if name != "why" and name not in g]
        if unread:
            raise BenchError(f"the configuration limits {unread}, which this family does not read")
        for name in g:
            if name in limits:
                compared.add(name, g[name], float(limits[name]))
        dropped = sum(c.get("moe_dropped", 0.0) for c in self.observed["counters"])
        compared.add("moe_dropped", float(dropped), 0.0)


def gaps(got: Dict[str, Any], want: Dict[str, Any]) -> Dict[str, float]:
    """``fedavg.gaps``' numbers, ``eval_gap`` among them only where the
    reference followed the evaluations. A configuration compares the
    ones its ``limits`` name: one that no reading separates (here the
    fp8 control's evaluation loss from the program's) gets no limit,
    and the reference then spends no time on its side of it."""
    pairs = lambda *keys: [harness.rel_gap(a, b) for k in keys for a, b in zip(got[k], want[k])]
    g = {"loss_gap": max(pairs("loss"))}
    if want["eval_test"]:
        g["eval_gap"] = max(pairs("eval_train", "eval_test"))
    g.update(
        first_norm_gap=harness.worst_leaf_gap(got["first_norms"], want["first_norms"]),
        change_norm_gap=harness.worst_leaf_gap(got["change_norms"], want["change_norms"]),
        packed_samples_gap=abs(got["packed_train_samples"] - want["packed_train_samples"]))
    return g
