#!/usr/bin/env python
"""Reproduce the reference's MNIST+LR FedAvg accuracy baseline.

Reference target: test acc 81.9 after 200 rounds — hyperparameters at
``doc/en/simulation/benchmark/BENCHMARK_simulation.md:15-35`` (1000
clients, 10/round, epochs 1, batch 10, SGD lr 0.03, hetero alpha 0.5).

Data strategy (in order):
1. a local LEAF copy under ``--data-cache-dir/mnist`` (use it as-is);
2. download the reference archive (constants.FEDML_DATA_MNIST_URL) —
   offline grace: failure falls through;
3. the bundled REAL handwritten-digits subset (UCI digits via
   scikit-learn, written in the exact MNIST LEAF layout —
   ``fedml_tpu/data/download.py``). It is ~1.4k train images over 100
   users, so the run is scaled (100 clients, 10/round) and the result
   is labeled ``dataset: digits_subset`` — a real-data learning
   trajectory, not an MNIST-scale reproduction.

Prints one JSON line: achieved final/best test acc, the 81.9 target,
and which data source actually backed the run. A centralized-training
anchor (``fedml_tpu.centralized.CentralizedTrainer``, the repo's CI
oracle) runs on the IDENTICAL data afterward, so on the subset — where
the 81.9 MNIST target is not comparable — the federated number is
interpretable as "within X pp of centralized on the same real data".

Usage (runs on whatever platform JAX_PLATFORMS selects; the output
records which):
    python scripts/reproduce_baseline.py [--rounds N] [--data-cache-dir D]
"""

import argparse
import json
import logging
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BASELINE_ACC = 81.9  # BENCHMARK_simulation.md:5


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--rounds", type=int, default=200)
    p.add_argument("--data-cache-dir", default="./fedml_data")
    p.add_argument("--test-freq", type=int, default=10)
    p.add_argument(
        "--centralized-epochs", type=int, default=-1,
        help="epoch budget for the centralized anchor on the same data "
        "(0 disables; -1 = auto: 40 on the digits subset where the 81.9 "
        "MNIST target is not comparable, 0 on full MNIST where it is "
        "and 40 epochs x 60k samples would waste hours on this box). "
        "The anchor makes the subset accuracy interpretable "
        "(federated-vs-centralized gap).",
    )
    a = p.parse_args()
    logging.basicConfig(level=logging.INFO)

    import fedml_tpu
    from fedml_tpu import models
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.data import load
    from fedml_tpu.data.leaf import leaf_available
    from fedml_tpu.data.download import download_mnist, materialize_real_digits
    from fedml_tpu.simulation import FedAvgAPI

    cache = os.path.abspath(a.data_cache_dir)
    mnist_dir = os.path.join(cache, "mnist")

    def is_digits_subset() -> bool:
        # provenance marker written by materialize_real_digits — a
        # subset from an earlier offline run must not be reported as
        # the real MNIST archive
        marker = os.path.join(mnist_dir, "_source.json")
        return os.path.isfile(marker) and not json.load(open(marker)).get(
            "is_mnist", True
        )

    digits_label = "digits_subset (bundled real data; NOT full MNIST)"
    source = None
    if leaf_available(mnist_dir):
        source = digits_label if is_digits_subset() else "mnist (local copy)"
    elif download_mnist(cache) and leaf_available(mnist_dir):
        source = "mnist (downloaded)"
    elif materialize_real_digits(cache) and leaf_available(mnist_dir):
        source = digits_label
    else:
        print(json.dumps({"error": "no real data source available"}))
        return

    full_mnist = source.startswith("mnist")
    args = Arguments()
    cfg = dict(
        # BENCHMARK_simulation.md:15-35, scaled to the subset when the
        # bundled digits back the run (100 users exist, not 1000)
        dataset="mnist",
        data_cache_dir=cache,
        partition_method="hetero",
        partition_alpha=0.5,
        model="lr",
        federated_optimizer="FedAvg",
        client_num_in_total=1000 if full_mnist else 100,
        client_num_per_round=10,
        comm_round=int(a.rounds),
        epochs=1,
        batch_size=10,
        client_optimizer="sgd",
        learning_rate=0.03,
        frequency_of_the_test=int(a.test_freq),
    )
    for k, v in cfg.items():
        setattr(args, k, v)
    args._validate()
    args = fedml_tpu.init(args)
    dataset = load(args)
    model = models.create(args, dataset.class_num)
    api = FedAvgAPI(args, None, dataset, model)
    final = api.train()

    import jax

    best = max((h.get("test_acc", 0.0) for h in api.history), default=0.0)
    out = {
        "metric": "mnist_lr_fedavg_test_acc",
        # platform provenance rides the JSON (repo rule: a CPU-backed
        # artifact must never read as an accelerator-backed one)
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "data_source": source,
        "real_data": True,
        "rounds": int(a.rounds),
        "final_test_acc_pct": round(100 * final.get("test_acc", 0.0), 2),
        "best_test_acc_pct": round(100 * best, 2),
        "baseline_acc_pct": BASELINE_ACC,
        "comparable_to_baseline": full_mnist,
    }

    anchor_epochs = (
        (0 if full_mnist else 40)
        if a.centralized_epochs < 0
        else a.centralized_epochs
    )
    if anchor_epochs > 0:
        # centralized anchor on the IDENTICAL dataset object: the same
        # jitted trainer the clients use, pointed at the global split
        # (centralized.py). Fresh Arguments so the federated run's
        # round config cannot leak into the anchor.
        from fedml_tpu.centralized import CentralizedTrainer

        cargs = Arguments()
        for k, v in cfg.items():
            setattr(cargs, k, v)
        cargs.epochs = int(anchor_epochs)
        cargs._validate()
        cmodel = models.create(cargs, dataset.class_num)
        trainer = CentralizedTrainer(cargs, None, dataset, cmodel)
        cfinal = trainer.train()
        cbest = max((h.get("test_acc", 0.0) for h in trainer.history), default=0.0)
        out["centralized_anchor"] = {
            "epochs": int(anchor_epochs),
            "final_test_acc_pct": round(100 * cfinal.get("test_acc", 0.0), 2),
            "best_test_acc_pct": round(100 * cbest, 2),
        }
        out["federated_minus_centralized_pp"] = round(100 * (best - cbest), 2)

    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
