"""Multi-controller MESH simulation: the client-parallel simulator's
global device mesh spanning 2 OS processes (jax.distributed), with the
FedAvg reduction as a cross-process all-reduce.

Oracle: identical final model to the single-process (one-controller)
simulation on the same data/config — process topology is a layout
choice. Combined with tests/test_mesh_simulator.py (mesh == single
chip) this closes the chain: SP == mesh == multi-host mesh.
"""

import os
import socket
import subprocess
import sys

import jax
import numpy as np
import pytest

# full tier only: multiprocess collectives are unsupported by this jaxlib's CPU backend, and the worlds are well over the 4s fast-gate budget
pytestmark = pytest.mark.slow

import fedml_tpu
from fedml_tpu import models
from fedml_tpu.data import load
from fedml_tpu.simulation import FedAvgAPI

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "mesh_mp_worker.py")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class TestMultiProcessMesh:
    def test_two_process_mesh_matches_sp(self, tmp_path, args_factory):
        out = str(tmp_path / "mesh_params.npz")
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + ":" + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4"
        )
        port = _free_port()
        procs = [
            subprocess.Popen(
                [
                    sys.executable, WORKER,
                    "--proc_rank", str(r),
                    "--n_proc", "2",
                    "--coordinator", f"127.0.0.1:{port}",
                    "--out", out,
                ],
                env=env,
            )
            for r in (0, 1)
        ]
        try:
            rcs = [p.wait(timeout=600) for p in procs]
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        assert rcs == [0, 0], f"mesh worker exit codes {rcs}"
        assert os.path.exists(out)

        args = args_factory(
            dataset="mnist",
            synthetic_train_size=512,
            synthetic_test_size=128,
            model="lr",
            partition_method="hetero",
            client_num_in_total=8,
            client_num_per_round=8,
            comm_round=2,
            epochs=1,
            batch_size=16,
            learning_rate=0.1,
            frequency_of_the_test=1,
            shuffle=False,
        )
        args = fedml_tpu.init(args)
        ds = load(args)
        model = models.create(args, ds.class_num)
        api = FedAvgAPI(args, None, ds, model)
        api.train()

        got = np.load(out)
        want = jax.tree.leaves(api.global_params)
        assert len(got.files) == len(want)
        for i, w in enumerate(want):
            np.testing.assert_allclose(
                got[f"p{i}"], np.asarray(w), atol=1e-5,
                err_msg=f"leaf {i}: 2-process mesh != single-process sim",
            )
