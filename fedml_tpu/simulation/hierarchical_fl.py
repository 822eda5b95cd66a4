"""Two-level (hierarchical) FedAvg.

Parity with ``python/fedml/simulation/single_process/hierarchical_fl/``:
``Group(FedAvgAPI)`` aggregates within a group every
``group_comm_round`` (group.py:7-60); ``Trainer(FedAvgAPI)`` aggregates
group models globally (trainer.py:10-110). Satisfies the CI oracle: with
full-batch clients and a fixed ``comm_round x group_comm_round``
product, hierarchical == flat == centralized
(ci/CI-script-fedavg.sh:53-63).

TPU-first: a group round reuses the SAME jitted round engine as flat
FedAvg (the cohort is the group), so group training is a vmapped
on-device computation; the global level is one more weighted pytree
reduction. Group partitioning is deterministic per seed.
"""

from __future__ import annotations

import logging
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from ..core.aggregation import normalize_weights, stack_pytrees, weighted_average
from .fedavg_api import FedAvgAPI


class HierarchicalFLAPI(FedAvgAPI):
    """args: ``group_num``, ``group_comm_round``; ``comm_round`` is the
    number of GLOBAL rounds (reference ``global_comm_round``)."""

    algorithm = "HierFedAvg"
    # group level consults the seam via _round_fn, but the global level
    # is a fixed group-weighted mean — mixed semantics, so reject
    _accepts_custom_aggregator = False

    def _groups(self) -> List[np.ndarray]:
        n = self.dataset.client_num
        gnum = int(getattr(self.args, "group_num", 2))
        rng = np.random.RandomState(int(getattr(self.args, "random_seed", 0)))
        method = getattr(self.args, "group_method", "random")
        idxs = rng.permutation(n) if method == "random" else np.arange(n)
        return [g.astype(np.int32) for g in np.array_split(idxs, gnum)]

    def train(self) -> Dict[str, float]:
        args = self.args
        packed = self._sample_store()
        self._eval_splits()
        nsamples = jnp.asarray(self.dataset.packed_num_samples)
        groups = self._groups()
        group_rounds = int(getattr(args, "group_comm_round", 1))
        freq = max(1, int(getattr(args, "frequency_of_the_test", 5)))
        final_stats: Dict[str, float] = {}
        for round_idx in range(int(args.comm_round)):
            t0 = time.perf_counter()
            self.rng, round_rng = jax.random.split(self.rng)
            # round-indexed LR decays with the GLOBAL round (constant
            # across a round's groups/group-rounds)
            lr_mult = self._lr_mult(round_idx)
            extra = () if lr_mult is None else (lr_mult,)
            group_params = []
            group_weights = []
            for gi, g in enumerate(groups):
                # donation-safe fresh start per group
                p = jax.tree.map(jnp.copy, self.global_params)
                state = self._init_server_state()
                for gr in range(group_rounds):
                    p, state, _ = self._round_fn(
                        p,
                        state,
                        packed,
                        nsamples,
                        jnp.asarray(g),
                        jax.random.fold_in(round_rng, gi * 1009 + gr),
                        *extra,
                    )
                group_params.append(p)
                group_weights.append(float(np.asarray(nsamples)[g].sum()))
            stacked = stack_pytrees(group_params)
            self.global_params = weighted_average(
                stacked, normalize_weights(jnp.asarray(group_weights))
            )
            if round_idx % freq == 0 or round_idx == int(args.comm_round) - 1:
                stats = self._local_test_on_all_clients(round_idx)
                stats["round"] = round_idx
                stats["round_time_s"] = time.perf_counter() - t0
                self.history.append(stats)
                final_stats = stats
                logging.info("hier round %d: %s", round_idx, stats)
        return final_stats
