"""Family driver: language-model training through ``DistributedTrainer``.

Drives ``DistributedTrainer(args, None, dataset, model).run()`` -- what
``fedml_tpu.run_distributed`` calls -- on a one-device ``dp`` mesh. The
benchmark makes, from ``--seed``: the weights (the reference's
``init_params``, handed over as the trainer's ``params``) and the
tokens (uniform over the whole vocabulary, every row different), held
in the program's plain ``FederatedDataset`` dataclass. The repo's own
sequence generator cannot make a real vocabulary (it draws a
vocab x vocab float64 matrix).

``run()`` takes an epoch count, not a duration. The program's epoch
executable exists twice: the first call takes the optimizer state as
``optax`` made it (a count on a single device), every later call takes
the first call's outputs (laid out on the mesh), and jit compiles that
again. So set-up first warms up with two one-epoch ``run()`` calls,
which build both, and only then hands the trainer the seed's weights
and a fresh optimizer state, every leaf placed as a ``run()`` output is
laid out. The check's drive is the next ``run()`` of one epoch: it goes
through the second executable, the one every call of the window runs,
and is what the reference follows. ``compiles_since_check`` counts the
executables built from there to the window's end and is held to 0, so
the program compared is the program timed. The window is filled with
``run()`` calls of ``epochs_per_call`` epochs; the rate is all trained
tokens over all of the window's wall time, data placement, the
per-epoch fetch and each call's closing evaluation included.
"""

from __future__ import annotations

import argparse
import time
from typing import Any, Dict

import numpy as np

import harness
from harness import BenchError


def synth_tokens(seed: int, nb: int, bs: int, t: int, vocab: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        tok = jax.random.randint(key, (nb, bs, t + 1), 0, vocab, jnp.int32)
        return tok[..., :-1], tok[..., 1:]

    return make(jax.random.PRNGKey(seed % (2 ** 31)))


def _host(tree):
    import jax

    return [np.asarray(a) for a in jax.tree.leaves(tree)]


class Driver:
    def __init__(self, cell: harness.Cell, seed: int) -> None:
        self.cell, self.seed = cell, int(seed)
        self.cfg, self.wl = cell.config, cell.traffic
        self.m, self.tr = self.cfg["model"], self.cfg["training"]
        self.ref = cell.module("reference", self.cfg["reference"])
        self.spans: Dict[str, float] = {}
        self.trainer = None

    def _args(self):
        from fedml_tpu.arguments import Arguments

        flat = dict(self.cfg["program_args"])
        flat.update(self.wl.get("program_args", {}))
        flat["random_seed"] = 0
        return Arguments(argparse.Namespace(**flat), training_type="distributed")

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        import fedml_tpu
        from fedml_tpu import models
        from fedml_tpu.core.types import Batches
        from fedml_tpu.data.loader import FederatedDataset
        from fedml_tpu.distributed import DistributedTrainer

        args = self.args = fedml_tpu.init(self._args())
        t0 = time.perf_counter()
        m, wl = self.m, self.wl
        nb, bs, t = int(wl["steps_per_epoch"]), int(self.tr["batch_size"]), int(self.tr["seq_len"])
        nb_te = int(wl["eval_batches"])
        self.train = synth_tokens(self.seed, nb, bs, t, m["vocab_size"])
        self.test = synth_tokens(self.seed + 1, nb_te, bs, t, m["vocab_size"])
        ones = lambda n: jnp.ones((n, bs), jnp.float32)
        ds = FederatedDataset(
            train_data_num=nb * bs, test_data_num=nb_te * bs,
            train_data_global=Batches(x=self.train[0], y=self.train[1], mask=ones(nb)),
            test_data_global=Batches(x=self.test[0], y=self.test[1], mask=ones(nb_te)),
            train_data_local_num_dict={}, train_data_local_dict={}, test_data_local_dict={},
            class_num=int(m["vocab_size"]), task="nwp",
        )
        self.tokens_per_epoch = nb * bs * t
        self.steps_per_epoch = nb
        self.spans["data_setup_s"] = time.perf_counter() - t0

        model = models.create(args, ds.class_num)
        trainer = self.trainer = DistributedTrainer(args, None, ds, model)

        self.w0 = self.ref.init_params(self.seed, m)
        want = jax.tree.map(lambda a: (a.shape, str(a.dtype)), self.w0)
        have = jax.tree.map(lambda a: (a.shape, str(a.dtype)), trainer.params)
        if want != have:
            raise BenchError(
                "the program's parameter tree is not the configuration's: "
                f"{jax.tree.structure(have)} vs {jax.tree.structure(want)}")

        # warm-up: both epoch executables and the evaluation, from the
        # program's own start
        t0 = time.perf_counter()
        self._set_call(1)
        trainer.run()
        trainer.run()
        self.spans["warmup_s"] = time.perf_counter() - t0

        # the benchmark's weights and a fresh optimizer state in the
        # program's place, laid out as run() hands its state back (the
        # old state is freed first: it would stand in the peak)
        t0 = time.perf_counter()
        laid = jax.tree.map(lambda a: a.sharding, (trainer.params, trainer.opt_state))
        trainer.params = trainer.opt_state = None
        place = lambda tree, shardings: jax.tree.map(jax.device_put, tree, shardings)
        trainer.params = place(jax.jit(lambda tr: jax.tree.map(jnp.copy, tr))(self.w0), laid[0])
        trainer.opt_state = place(trainer.optimizer.init(trainer.params), laid[1])

        # the check's drive: run() of one epoch, through the executable
        # the window runs
        self.compiles_at_check = harness.compile_count()
        stats = trainer.run()
        mu = [s.mu for s in jax.tree.leaves(
            trainer.opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
        if len(mu) != 1:
            raise BenchError("the trainer's optimizer state carries no single first moment")
        self.observed = {
            "loss": float(stats["train_loss"]),
            "eval": float(stats["test_loss"]),
            "params": _host(trainer.params),
            "mu": _host(mu[0]),
        }
        self.compiles_after_check = harness.compile_count()
        self.spans["check_drive_s"] = time.perf_counter() - t0
        self._set_call(int(wl["epochs_per_call"]))

    def _set_call(self, epochs: int) -> None:
        self.args.epochs = int(epochs)
        self.args.frequency_of_the_test = 10 ** 9

    def window(self, seconds: float) -> Dict[str, Any]:
        import jax

        trainer = self.trainer
        per_call = int(self.wl["epochs_per_call"])
        t0 = time.perf_counter()
        call_ends = []
        bad = 0
        while time.perf_counter() - t0 < seconds:
            stats = trainer.run()
            call_ends.append(time.perf_counter() - t0)
            if not all(np.isfinite(stats[k]) for k in ("train_loss", "test_loss")):
                bad += 1
        calls = len(call_ends)
        jax.block_until_ready(trainer.params)
        wall = time.perf_counter() - t0
        self.observed["compiles_since_check"] = float(
            harness.compile_count() - self.compiles_at_check)
        epochs = calls * per_call
        return {
            "wall_s": wall,
            "units": epochs * self.steps_per_epoch,
            "failed": bad * per_call * self.steps_per_epoch,
            "steps": epochs * self.steps_per_epoch,
            "epochs": epochs,
            "calls": calls,
            "call_s": [b - a for a, b in zip([0.0] + call_ends, call_ends)],
            "tokens": float(epochs * self.tokens_per_epoch),
            "eval_tokens": float(calls * self.test[0].size),
        }

    def end_to_end(self, win: Dict[str, Any]) -> Dict[str, float]:
        return {"tokens_per_s": win["tokens"] / win["wall_s"]}

    def release(self) -> None:
        import jax

        self.trainer.params = None
        self.trainer.opt_state = None
        self.trainer.dataset = None
        self.trainer = None
        jax.clear_caches()

    def reference_numbers(self, quant=None, row_keep: int = 0) -> Dict[str, Any]:
        import jax

        with jax.default_matmul_precision("highest"):
            w, mu, losses = self.ref.train_epoch(
                self.w0, self.train, self.m, float(self.tr["lr"]), quant=quant, row_keep=row_keep)
            ev = self.ref.evaluate(w, self.test, self.m, quant)
        return {
            "loss": float(np.mean(losses)), "eval": ev,
            "params": _host(w), "mu": _host(mu), "step_losses": losses,
        }

    def gaps(self, got: Dict[str, Any], want: Dict[str, Any]) -> Dict[str, float]:
        return gaps(got, want, _host(self.w0))

    def compare(self, compared: harness.Compared) -> None:
        limits = self.cfg["limits"]
        g = self.gaps(self.observed, self.reference_numbers())
        for name in ("loss_gap", "eval_gap", "moment_norm_gap", "change_norm_gap"):
            compared.add(name, g[name], float(limits[name]))
        compared.add("compiles_since_check", self.observed["compiles_since_check"], 0.0)

    def facts(self) -> Dict[str, Any]:
        from fedml_tpu.core import compile_cache

        return {
            "spans": dict(self.spans),
            "counters": {"compile_cache_misses": float(compile_cache.stats()["misses"])},
        }


def gaps(got: Dict[str, Any], want: Dict[str, Any], w0) -> Dict[str, float]:
    """The four numbers an LM cell compares. Elements whose gradient is
    nought to rounding in the reference (a key's bias under softmax, an
    embedding row no token touched) move under Adam by round-off alone:
    they are left out of both norms by a rule on the reference's own
    first moment -- under a thousandth of the median leaf's RMS."""
    rms = [float(np.sqrt(np.mean(np.square(a, dtype=np.float64)))) for a in want["mu"]]
    floor = 1e-3 * float(np.median(rms))
    keep = [np.abs(a) >= floor for a in want["mu"]]

    def norms(leaves, base=None):
        out = []
        for i, a in enumerate(leaves):
            d = a.astype(np.float64) - (0.0 if base is None else base[i].astype(np.float64))
            out.append(float(np.sqrt(np.sum(np.square(d) * keep[i]))))
        return out

    return {
        "loss_gap": harness.rel_gap(got["loss"], want["loss"]),
        "eval_gap": harness.rel_gap(got["eval"], want["eval"]),
        "moment_norm_gap": harness.worst_leaf_gap(norms(got["mu"]), norms(want["mu"])),
        "change_norm_gap": harness.worst_leaf_gap(norms(got["params"], w0), norms(want["params"], w0)),
    }
