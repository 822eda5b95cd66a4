"""Decentralized gossip SGD (DSGD / PushSum).

Parity with ``python/fedml/simulation/single_process/decentralized/``
(``ClientDSGD`` client_dsgd.py:6, ``ClientPushsum``) over the topology
managers (SURVEY.md §2.5), and with the MPI gossip worker
(``mpi_p2p_mp/decentralized_framework/decentralized_worker_manager.py:8-50``).

TPU-first redesign: all N nodes' params live stacked on device
[N, ...]; one gossip round is
  (1) vmapped local training of every node, then
  (2) ONE mixing matmul  theta <- W @ theta  (einsum over the node
      axis — the entire network's neighbor-weighted averaging in a
      single MXU pass, replacing the reference's per-node loops and
      per-edge messages).
PushSum keeps the scalar mass vector w and de-biases with theta/w.
"""

from __future__ import annotations

import logging
import time
from typing import Dict

import jax
import jax.numpy as jnp

from ..core.topology import AsymmetricTopologyManager, SymmetricTopologyManager
from .fedavg_api import FedAvgAPI


def _mix(stacked, W):
    """theta_i <- sum_j W[i,j] theta_j over the stacked node axis."""
    return jax.tree.map(
        lambda l: jnp.einsum("ij,j...->i...", W.astype(l.dtype), l), stacked
    )


class DecentralizedDSGDAPI(FedAvgAPI):
    """Symmetric gossip (ClientDSGD semantics). All clients participate
    every round (there is no server)."""

    algorithm = "DSGD"
    directed = False
    supports_mesh = False  # node axis sizing vs mesh padding; later round

    def __init__(self, args, device, dataset, model, mesh=None) -> None:
        super().__init__(args, device, dataset, model, mesh)
        if self._round_lr is not None:
            raise ValueError(
                "round-indexed lr_schedule is not supported for "
                "decentralized gossip (no server round clock); use "
                "lr_schedule=constant"
            )
        n = dataset.client_num
        packed_rows = int(dataset.packed_train.mask.shape[0])
        if packed_rows != n:
            raise ValueError(
                f"decentralized gossip needs one node per packed client "
                f"(got {packed_rows} packed rows for {n} clients)"
            )
        if self.directed:
            topo = AsymmetricTopologyManager(
                n,
                neighbor_num=int(getattr(args, "topology_neighbor_num", 2)),
                seed=int(getattr(args, "random_seed", 0)),
            )
        else:
            topo = SymmetricTopologyManager(
                n,
                neighbor_num=int(getattr(args, "topology_neighbor_num", 2)),
                beta=float(getattr(args, "topology_beta", 0.0)),
                seed=int(getattr(args, "random_seed", 0)),
            )
        topo.generate_topology()
        self.topology = topo
        self.W = topo.mixing_matrix()

        # per-node params, all starting from the same init
        self.node_params = jax.tree.map(
            lambda l: jnp.broadcast_to(l[None], (n,) + l.shape), self.global_params
        )

        def gossip_round(node_params, packed, rng, W):
            rngs = jax.random.split(rng, packed.mask.shape[0])
            new_stacked, metrics = jax.vmap(self._local_train, in_axes=(0, 0, 0))(
                node_params, packed, rngs
            )
            return _mix(new_stacked, W), metrics

        self._gossip_fn = jax.jit(gossip_round, donate_argnums=(0,))

        def consensus(node_params):
            mean = jax.tree.map(lambda l: l.mean(axis=0), node_params)
            dis = sum(
                jnp.sum(jnp.square(l - m[None]))
                for l, m in zip(
                    jax.tree.leaves(node_params), jax.tree.leaves(mean)
                )
            )
            return mean, dis

        self._consensus = jax.jit(consensus)

    def train(self) -> Dict[str, float]:
        args = self.args
        packed = self.dataset.packed_train
        self._eval_splits()  # staged before the first round, not inside it
        freq = max(1, int(getattr(args, "frequency_of_the_test", 5)))
        final_stats: Dict[str, float] = {}
        for round_idx in range(int(args.comm_round)):
            t0 = time.perf_counter()
            self.rng, r = jax.random.split(self.rng)
            self.node_params, _ = self._gossip_fn(self.node_params, packed, r, self.W)
            if round_idx % freq == 0 or round_idx == int(args.comm_round) - 1:
                mean, disagreement = self._consensus(self.node_params)
                self.global_params = mean
                stats = self._local_test_on_all_clients(round_idx)
                stats["round"] = round_idx
                stats["consensus_dist"] = float(disagreement)
                stats["round_time_s"] = time.perf_counter() - t0
                self.history.append(stats)
                final_stats = stats
                logging.info("dsgd round %d: %s", round_idx, stats)
        return final_stats


class DecentralizedPushSumAPI(DecentralizedDSGDAPI):
    """Directed-graph gossip with PushSum weight correction
    (ClientPushsum semantics: column-stochastic mixing, de-bias by the
    gossiped scalar mass)."""

    algorithm = "PushSum"
    directed = True

    def __init__(self, args, device, dataset, model, mesh=None) -> None:
        # (the round-LR refusal lives in the DSGD parent __init__)
        super().__init__(args, device, dataset, model, mesh)
        n = dataset.client_num
        self.mass = jnp.ones((n,))

        def pushsum_round(node_params, mass, packed, rng, W):
            rngs = jax.random.split(rng, packed.mask.shape[0])
            # train on de-biased estimates x = z / w
            debiased = jax.tree.map(
                lambda l: l / mass.reshape((-1,) + (1,) * (l.ndim - 1)), node_params
            )
            new_stacked, metrics = jax.vmap(self._local_train, in_axes=(0, 0, 0))(
                debiased, packed, rngs
            )
            # re-bias, then push
            rebiased = jax.tree.map(
                lambda l: l * mass.reshape((-1,) + (1,) * (l.ndim - 1)), new_stacked
            )
            mixed = _mix(rebiased, W)
            new_mass = W @ mass
            return mixed, new_mass, metrics

        self._pushsum_fn = jax.jit(pushsum_round, donate_argnums=(0, 1))

    def train(self) -> Dict[str, float]:
        args = self.args
        packed = self.dataset.packed_train
        self._eval_splits()  # staged before the first round, not inside it
        freq = max(1, int(getattr(args, "frequency_of_the_test", 5)))
        final_stats: Dict[str, float] = {}
        for round_idx in range(int(args.comm_round)):
            t0 = time.perf_counter()
            self.rng, r = jax.random.split(self.rng)
            self.node_params, self.mass, _ = self._pushsum_fn(
                self.node_params, self.mass, packed, r, self.W
            )
            if round_idx % freq == 0 or round_idx == int(args.comm_round) - 1:
                debiased = jax.tree.map(
                    lambda l: l / self.mass.reshape((-1,) + (1,) * (l.ndim - 1)),
                    self.node_params,
                )
                mean, disagreement = self._consensus(debiased)
                self.global_params = mean
                stats = self._local_test_on_all_clients(round_idx)
                stats["round"] = round_idx
                stats["consensus_dist"] = float(disagreement)
                stats["round_time_s"] = time.perf_counter() - t0
                self.history.append(stats)
                final_stats = stats
                logging.info("pushsum round %d: %s", round_idx, stats)
        return final_stats
