"""Examples smoke: every shipped example runs end to end as a user
would run it (subprocess, --cf yaml), on forced-CPU virtual devices.

Reference analog: ``test/fedml_user_code/`` — runnable copies of the
one-line examples per platform (SURVEY.md §4 "user-journey tests").
"""

import os
import shutil
import socket
import subprocess
import sys

import pytest

# full tier only: end-to-end example runs, minutes on a 1-core box
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(REPO, "examples")


def _env(devices=1):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + ":" + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices}"
    )
    return env


def _free_port_block(n=4):
    import random

    rng = random.Random()
    for _ in range(50):
        base = rng.randint(20000, 55000)
        socks = []
        try:
            for i in range(n):
                s = socket.socket()
                s.bind(("127.0.0.1", base + i))
                socks.append(s)
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise RuntimeError("no free port block")


def _run(cmd, cwd, env, timeout=300):
    r = subprocess.run(
        cmd, cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout
    )
    assert r.returncode == 0, f"{cmd} failed:\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}"
    return r


def _patched_config(src_dir, tmp_path, port_base=None):
    """Copy an example dir to tmp (examples write nothing, but port
    overrides need a private yaml)."""
    dst = tmp_path / os.path.basename(src_dir)
    shutil.copytree(src_dir, dst)
    cfg = dst / "fedml_config.yaml"
    if port_base is not None:
        text = cfg.read_text().replace(
            "grpc_port_base: 8890", f"grpc_port_base: {port_base}"
        )
        cfg.write_text(text)
    return str(dst)


class TestSimulationExamples:
    def test_sp_one_line(self):
        d = os.path.join(EXAMPLES, "simulation_sp", "one_line")
        r = _run(
            [sys.executable, "main.py", "--cf", "fedml_config.yaml"],
            cwd=d, env=_env(),
        )
        assert "FINAL:" in r.stdout

    def test_sp_custom_operator(self):
        d = os.path.join(EXAMPLES, "simulation_sp", "custom")
        r = _run(
            [sys.executable, "main.py", "--cf", "fedml_config.yaml"],
            cwd=d, env=_env(),
        )
        assert "FINAL:" in r.stdout

    def test_mesh_one_line_8_devices(self):
        d = os.path.join(EXAMPLES, "simulation_mesh", "one_line")
        r = _run(
            [sys.executable, "main.py", "--cf", "fedml_config.yaml"],
            cwd=d, env=_env(devices=8),
        )
        assert "FINAL:" in r.stdout

    def test_distributed_one_line_8_devices(self):
        d = os.path.join(EXAMPLES, "distributed", "one_line")
        r = _run(
            [sys.executable, "main.py", "--cf", "fedml_config.yaml"],
            cwd=d, env=_env(devices=8), timeout=580,
        )
        assert "FINAL:" in r.stdout

    def test_distributed_step_by_step_8_devices(self):
        d = os.path.join(EXAMPLES, "distributed", "step_by_step")
        r = _run(
            [sys.executable, "main.py", "--cf", "fedml_config.yaml"],
            cwd=d, env=_env(devices=8), timeout=580,
        )
        assert "FINAL:" in r.stdout

    def test_longcontext_one_line_8_devices(self):
        d = os.path.join(EXAMPLES, "longcontext", "one_line")
        r = _run(
            [sys.executable, "main.py", "--cf", "fedml_config.yaml"],
            cwd=d, env=_env(devices=8), timeout=580,
        )
        assert "FINAL:" in r.stdout


class TestCrossSiloExample:
    @pytest.mark.parametrize("tier", ["one_line", "step_by_step", "custom"])
    def test_server_two_clients_grpc(self, tmp_path, tier):
        """All tiers run identically — step_by_step IS one_line's five
        stages (init/device/data/model/runner) spelled out; custom
        plugs L3 operator subclasses into the same runners."""
        base = _free_port_block(4)
        d = _patched_config(
            os.path.join(EXAMPLES, "cross_silo", tier), tmp_path, base
        )
        env = _env()
        clients = [
            subprocess.Popen(
                [sys.executable, "client.py", "--cf", "fedml_config.yaml",
                 "--rank", str(r)],
                cwd=d, env=env,
            )
            for r in (1, 2)
        ]
        try:
            _run(
                [sys.executable, "server.py", "--cf", "fedml_config.yaml",
                 "--rank", "0"],
                cwd=d, env=env,
            )
            rcs = [c.wait(timeout=60) for c in clients]
            assert rcs == [0, 0]
        finally:
            for c in clients:
                if c.poll() is None:
                    c.kill()


class TestHierarchicalExample:
    def test_server_two_silo_clients(self, tmp_path):
        base = _free_port_block(4)
        d = _patched_config(
            os.path.join(EXAMPLES, "cross_silo_hierarchical", "one_line"),
            tmp_path, base,
        )
        env = _env(devices=2)  # each silo data-shards over 2 devices
        clients = [
            subprocess.Popen(
                [sys.executable, "client.py", "--cf", "fedml_config.yaml",
                 "--rank", str(r)],
                cwd=d, env=env,
            )
            for r in (1, 2)
        ]
        try:
            _run(
                [sys.executable, "server.py", "--cf", "fedml_config.yaml",
                 "--rank", "0"],
                cwd=d, env=env,
            )
            rcs = [c.wait(timeout=60) for c in clients]
            assert rcs == [0, 0]
        finally:
            for c in clients:
                if c.poll() is None:
                    c.kill()


class TestCrossDeviceExample:
    def test_beehive_main(self):
        d = os.path.join(EXAMPLES, "cross_device", "one_line")
        r = _run(
            [sys.executable, "main.py", "--cf", "fedml_config.yaml"],
            cwd=d, env=_env(),
        )
        assert "FINAL:" in r.stdout
