"""Silo process launcher.

Parity with ``cross_silo/hierarchical/dist_trainer_launcher.py:23-48``:
the reference spawns per-node ``torchrun --rdzv_backend=c10d`` via pdsh
over ssh. Here a silo's processes are plain OS processes that rendezvous
through ``jax.distributed`` (coordinator = process 0), so the launcher
is ordinary ``subprocess`` + env plumbing: one child per silo process,
each told its ``proc_rank_in_silo`` / coordinator / fabric ports.

Single-host only (this environment has no ssh fan-out); multi-host
deployments run the same entry script per host with the same arguments,
exactly like torchrun's per-node invocation.
"""

from __future__ import annotations

import os
import subprocess
import sys
from typing import Dict, List, Optional, Sequence


def launch_silo_processes(
    entry_script: str,
    n_proc_in_silo: int,
    coordinator_port: int,
    silo_grpc_port_base: int,
    extra_argv: Sequence[str] = (),
    env_overrides: Optional[Dict[str, str]] = None,
    local_devices_per_proc: Optional[int] = None,
) -> List[subprocess.Popen]:
    """Spawn ``n_proc_in_silo`` OS processes running ``entry_script``.

    Each child receives ``--proc_rank_in_silo r --n_proc_in_silo N
    --distributed_coordinator 127.0.0.1:<port> --silo_grpc_port_base
    <base>`` plus ``extra_argv``. Caller waits on the returned Popens
    (process 0 is the master and the jax.distributed coordinator).

    ``local_devices_per_proc``: when set, forces that many virtual CPU
    devices per child (the test harness). Without it the children take
    the platform the environment gives them — and on a chip host that
    is refused for more than one process: a chip belongs to one
    process, every child would claim all local chips, and all but one
    would fail or hang. Run one silo process per chip host (this
    function's multi-host analog), or place the children on the CPU.
    """
    if n_proc_in_silo > 1 and not local_devices_per_proc:
        platforms = (env_overrides or {}).get(
            "JAX_PLATFORMS", os.environ.get("JAX_PLATFORMS", "")
        )
        if platforms != "cpu":
            raise RuntimeError(
                f"launch_silo_processes: {n_proc_in_silo} processes on one "
                f"host with JAX_PLATFORMS={platforms!r} would each claim "
                "every local accelerator chip, and a chip belongs to one "
                "process. Pass local_devices_per_proc (virtual CPU devices), "
                "set JAX_PLATFORMS=cpu, or start one process per chip host."
            )
    procs: List[subprocess.Popen] = []
    for r in range(n_proc_in_silo):
        env = dict(os.environ)
        if local_devices_per_proc:
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={local_devices_per_proc}"
            )
        if env_overrides:
            env.update(env_overrides)
        cmd = [
            sys.executable,
            entry_script,
            "--proc_rank_in_silo",
            str(r),
            "--n_proc_in_silo",
            str(n_proc_in_silo),
            "--distributed_coordinator",
            f"127.0.0.1:{coordinator_port}",
            "--silo_grpc_port_base",
            str(silo_grpc_port_base),
            *extra_argv,
        ]
        procs.append(subprocess.Popen(cmd, env=env))
    return procs
