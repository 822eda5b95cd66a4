"""fedml_tpu — a TPU-native federated / distributed learning framework.

Brand-new design with the capability surface of FedML v0.7.39
(reference layout: SURVEY.md; one-line API parity with
``python/fedml/__init__.py``): ``init()`` -> ``device`` -> ``data`` ->
``model`` -> scenario ``run()``. Compute is JAX/XLA end-to-end — client
updates are jitted scans, cohorts are vmapped/mesh-sharded, aggregation
is an on-device reduction — so the FL round loop never round-trips
through host pickles the way the reference does.
"""

from __future__ import annotations

import logging
import random as _random
from typing import Optional

import numpy as np

from . import constants  # noqa: F401
from .arguments import Arguments, load_arguments

__version__ = "0.1.0"

# The L3 operator seam (core.frame) imports JAX transitively; loading
# it lazily (PEP 562) keeps `import fedml_tpu` — and therefore the
# pure-AST `fedml-tpu lint` CLI — free of any JAX import. Training
# entry points touch these names (or core.frame directly) and pull
# JAX in at that point, exactly as before.
_LAZY_FRAME_EXPORTS = (
    "ClientTrainer",
    "DefaultClientTrainer",
    "DefaultServerAggregator",
    "ServerAggregator",
)


def __getattr__(name: str):
    if name in _LAZY_FRAME_EXPORTS:
        from .core import frame

        return getattr(frame, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + list(_LAZY_FRAME_EXPORTS))

_global_training_type: Optional[str] = None
_global_comm_backend: Optional[str] = None


def init(args: Optional[Arguments] = None) -> Arguments:
    """Parity with ``fedml.init()`` (__init__.py:34-136): load args,
    seed RNGs, set numeric precision, resolve per-scenario process
    identity."""
    if args is None:
        args = load_arguments(_global_training_type, _global_comm_backend)
    _seed(int(getattr(args, "random_seed", 0)))
    import jax

    jax.config.update(
        "jax_default_matmul_precision",
        getattr(args, "matmul_precision", "highest"),
    )
    from .parallel.layout import fed_mesh_shape

    if fed_mesh_shape(getattr(args, "mesh_shape", None)) and not (
        jax.config.jax_threefry_partitionable
    ):
        # fed (data, fsdp) mesh runs need SHARDING-INVARIANT random
        # draws (the partitionable threefry) for the mesh-vs-single-
        # chip bitwise identity; flipped here — before any data
        # synthesis — so every world this process builds draws from
        # the same stream (parallel/layout.py explains the hazard)
        logging.info(
            "mesh_shape=%s: enabling jax_threefry_partitionable "
            "(sharding-invariant random draws)", args.mesh_shape,
        )
        jax.config.update("jax_threefry_partitionable", True)
    logging.getLogger().setLevel(
        logging.DEBUG if getattr(args, "verbose", False) else logging.INFO
    )
    if args.training_type == constants.FEDML_TRAINING_PLATFORM_SIMULATION:
        args.process_id = 0
    elif args.training_type == constants.FEDML_TRAINING_PLATFORM_CROSS_SILO:
        args.process_id = int(getattr(args, "rank", 0))
        if getattr(args, "distributed_coordinator", None):
            # multi-controller hierarchical silo: join the runtime's
            # process group BEFORE anything initializes the backend
            # (the torchrun-env analog, reference __init__.py:85-130)
            from .cross_silo.hierarchical.process_group_manager import (
                ensure_distributed_initialized,
            )

            ensure_distributed_initialized(args)
    elif args.training_type == constants.FEDML_TRAINING_PLATFORM_CROSS_DEVICE:
        args.rank = 0
        args.process_id = 0
    # persistent compilation cache (core/compile_cache.py) — here, after
    # any process-group join and before the data loader's synthesis
    # jits, so every compile of the run can be served from it
    from .core.compile_cache import maybe_enable_compile_cache

    maybe_enable_compile_cache(args)
    return args


def _seed(seed: int) -> None:
    _random.seed(seed)
    np.random.seed(seed)


def run_simulation(
    backend: str = constants.FEDML_SIMULATION_TYPE_SP,
    client_trainer=None,
    server_aggregator=None,
) -> None:
    """One-line simulation entry (__init__.py:139-169). Custom L3
    operators (``core.frame``) plug in via ``client_trainer=`` /
    ``server_aggregator=``."""
    global _global_training_type, _global_comm_backend
    _global_training_type = constants.FEDML_TRAINING_PLATFORM_SIMULATION
    _global_comm_backend = backend

    from . import data, device, models
    from .simulation import SimulatorMesh, SimulatorSingleProcess

    args = init()
    dev = device.get_device(args)
    dataset = data.load(args)
    model = models.create(args, dataset.class_num)
    if backend in (
        constants.FEDML_SIMULATION_TYPE_MESH,
        constants.FEDML_SIMULATION_TYPE_NCCL,
    ):
        simulator = SimulatorMesh(
            args, dev, dataset, model,
            client_trainer=client_trainer, server_aggregator=server_aggregator,
        )
    elif backend == constants.FEDML_SIMULATION_TYPE_SP:
        simulator = SimulatorSingleProcess(
            args, dev, dataset, model,
            client_trainer=client_trainer, server_aggregator=server_aggregator,
        )
    else:
        raise ValueError(f"unknown simulation backend {backend!r}")
    return simulator.run()


def run_distributed(args: Optional[Arguments] = None):
    """One-line mesh-parallel (distributed) LM training — the
    ``training_type: distributed`` platform. The YAML's ``mesh_shape``
    picks the parallelism (dp x tp x ep, sp, or pp); see
    ``fedml_tpu.distributed``. No reference counterpart: this is where
    the green-field parallel subsystems surface as product."""
    global _global_training_type
    _global_training_type = constants.FEDML_TRAINING_PLATFORM_DISTRIBUTED
    from . import data, device, models
    from .distributed import DistributedTrainer

    args = init(args)
    dev = device.get_device(args)
    dataset = data.load(args)
    model = models.create(args, dataset.class_num)
    return DistributedTrainer(args, dev, dataset, model).run()


def run_cross_silo_server(args: Optional[Arguments] = None, server_aggregator=None):
    """One-line cross-silo server (__init__.py:172-191)."""
    global _global_training_type
    _global_training_type = constants.FEDML_TRAINING_PLATFORM_CROSS_SILO
    from . import data, device, models
    from .cross_silo import Server

    args = init(args)
    dev = device.get_device(args)
    dataset = data.load(args)
    model = models.create(args, dataset.class_num)
    server = Server(args, dev, dataset, model, server_aggregator=server_aggregator)
    from .core.tracking import device_trace

    with device_trace(args):
        return server.run()


def run_cross_silo_client(args: Optional[Arguments] = None, client_trainer=None):
    """One-line cross-silo client (__init__.py:193-211)."""
    global _global_training_type
    _global_training_type = constants.FEDML_TRAINING_PLATFORM_CROSS_SILO
    from . import data, device, models
    from .cross_silo import Client

    args = init(args)
    dev = device.get_device(args)
    dataset = data.load(args)
    model = models.create(args, dataset.class_num)
    client = Client(args, dev, dataset, model, client_trainer=client_trainer)
    from .core.tracking import device_trace

    with device_trace(args):
        return client.run()


def run_hierarchical_cross_silo_server(
    args: Optional[Arguments] = None, server_aggregator=None
):
    """One-line hierarchical cross-silo server (__init__.py:214-233).
    Protocol-identical to the horizontal server — the hierarchy lives
    entirely client-side (each FL client is a sharded training group)."""
    return run_cross_silo_server(args, server_aggregator=server_aggregator)


def run_hierarchical_cross_silo_client(
    args: Optional[Arguments] = None, client_trainer=None
):
    """One-line hierarchical cross-silo client (__init__.py:235-253):
    master/slave role follows ``args.proc_rank_in_silo`` the way the
    reference forks on the torchrun-derived process rank."""
    global _global_training_type
    _global_training_type = constants.FEDML_TRAINING_PLATFORM_CROSS_SILO
    from . import data, device, models
    from .cross_silo import HierarchicalClient

    args = init(args)
    dev = device.get_device(args)
    dataset = data.load(args)
    model = models.create(args, dataset.class_num)
    client = HierarchicalClient(args, dev, dataset, model, client_trainer=client_trainer)
    from .core.tracking import device_trace

    with device_trace(args):
        return client.run()


def run_edge_server(args: Optional[Arguments] = None):
    """One-line cross-device server — the ``run_mnn_server`` analog
    (__init__.py:256-274): edge clients ship model files over the
    pub/sub data plane; the server aggregates on TPU."""
    global _global_training_type
    _global_training_type = constants.FEDML_TRAINING_PLATFORM_CROSS_DEVICE
    from . import data, device, models
    from .cross_device import ServerEdge

    args = init(args)
    dev = device.get_device(args)
    dataset = data.load(args)
    model = models.create(args, dataset.class_num)
    server = ServerEdge(args, dev, dataset, model)
    from .core.tracking import device_trace

    with device_trace(args):
        return server.run()
