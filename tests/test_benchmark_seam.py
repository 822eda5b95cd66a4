"""The seam between the program and the benchmark that measures it
(``BENCHMARK.json`` + ``benchmark/``; nothing there is edited here, the
files are read). The benchmark's trace readers find the program's
executables and kernels by name, so a rename on the program's side
shows first as a ``null`` per-layer metric on the chip; and the rule
that a CPU yields no rate has to hold for the benchmark that counts.
"""

import ast
import glob
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import fedml_tpu
from fedml_tpu import constants, models
from fedml_tpu.data import load
from fedml_tpu.ops.flash_attention import flash_attention
from fedml_tpu.simulation import FedAvgAPI

pytestmark = pytest.mark.smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(*parts) -> str:
    with open(os.path.join(REPO, *parts)) as fh:
        return fh.read()


CELLS = [w["name"] for w in json.loads(_read("BENCHMARK.json"))["workloads"]]


def test_benchmark_refuses_a_cpu_before_building_anything():
    """``benchmark/run.py`` on a CPU: exit 2, nothing on stdout, the
    platform named on stderr — and ``run_cell``, which builds the data
    and the model, is never reached."""
    prog = (
        "import sys; sys.path.insert(0, 'benchmark'); import run\n"
        "def reached(*a, **k): sys.exit(99)\n"
        "run.run_cell = reached\n"
        "sys.exit(run.main(['--workload', 'fedavg_r18_c32', '--seed', '1',"
        " '--seconds', '1']))\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", prog], cwd=REPO, capture_output=True,
        text=True, timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 2, (r.returncode, r.stderr[-800:])
    assert r.stdout == ""
    assert "needs a TPU" in r.stderr and "'cpu'" in r.stderr


class _Lowered(Exception):
    pass


@pytest.mark.parametrize(
    "attr, name", [("_round_fn", "jit_round_fn"), ("_eval_all", "jit_eval_all")]
)
def test_executables_lower_under_the_names_the_trace_readers_look_up(
    args_factory, attr, name
):
    """``round_fn_device_ms`` and ``eval_device_ms`` find their
    executable by its module name; lowered with the arguments
    ``train()`` itself passes: the sample store, and for the evaluation
    the accessor's split (hetero clients: shorter than the packing)."""
    args = fedml_tpu.init(args_factory(
        dataset="mnist", synthetic_train_size=120, synthetic_test_size=40,
        model="lr", client_num_in_total=4, client_num_per_round=2,
        comm_round=1, epochs=1, batch_size=10, frequency_of_the_test=1,
        partition_method="hetero", partition_alpha=0.1,
    ))
    ds = load(args)
    api = FedAvgAPI(args, None, ds, models.create(args, ds.class_num))
    jitted, passed = getattr(api, attr), []

    def lower_and_stop(*a, **kw):
        passed.extend(a)
        raise _Lowered(jitted.lower(*a, **kw).as_text())

    setattr(api, attr, lower_and_stop)
    with pytest.raises(_Lowered) as ei:
        api.train()
    assert f"module @{name} " in str(ei.value)
    if attr == "_eval_all":
        assert passed[1] is api._eval_splits()[0]
        assert passed[1].num_batches < ds.packed_train.num_batches
    else:
        assert passed[2] is api._sample_store()


KERNELS = [
    "flash_attention_fwd", "flash_attention_bwd_dkv", "flash_attention_bwd_dq",
    "flash_attention_window_fwd", "flash_attention_window_bwd_dkv",
    "flash_attention_window_bwd_dq",
]


@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_is_named_in_the_tpu_lowering(kernel):
    """The roofline readers find a kernel's device time by the name
    Mosaic gives its custom call: a forward and backward, lowered for
    the TPU from here, with a window and without."""
    window = 128 if "window" in kernel else None

    def loss(q, k, v):
        return flash_attention(q, k, v, True, window=window).astype(
            jnp.float32).sum()

    s = jax.ShapeDtypeStruct((1, 256, 2, 64), jnp.bfloat16)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(s, s, s).lower(
        lowering_platforms=("tpu",)).as_text()
    assert kernel in set(re.findall(r"flash_attention\w*", text))
    for traffic in ("c2_t4096_b4_eval10.json", "c2_t4096_b2_eval10.json", "c2_t8192_b1_eval10.json"):
        read = json.loads(_read("benchmark", "workloads", traffic))["kernel_names"]
        assert set(read) <= set(KERNELS)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_is_described_where_readers_look(cell):
    assert f"`{cell}`" in _read("docs", "benchmarks.md")
    perf = _read("PERF.md")
    cells_section = perf[perf.index("## 4. Cells"):perf.index("## 5.")]
    assert f"`{cell}`" in cells_section


@pytest.mark.parametrize("rehearsal", ["test_fedavg_lfm2.py", "test_fedavg_twotower.py"])
def test_the_lane_after_lane_familys_tiny_cell_runs_on_the_cpu(rehearsal):
    """``benchmark/tests/`` is outside this suite's command (PERF.md
    section 7, ask 10), so the rehearsal of the ``fedavg_lm_lanes``
    family's whole run (and of ``fedavg_lm_lanes_ssm``'s, its child) --
    set-up, window, release, the comparison with its plain reference --
    is started from here, in a process of its own (the benchmark's
    tests bring their own ``conftest.py``)."""
    r = subprocess.run(
        [sys.executable, "-m", "pytest", f"benchmark/tests/{rehearsal}::test_end_to_end_line",
         "-q", "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"],
        cwd=REPO, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0 and "1 passed" in r.stdout, (r.stdout[-1500:], r.stderr[-800:])


def test_every_test_the_ci_scripts_name_exists():
    """``set -e`` catches a node id that no longer collects only when
    someone runs the script."""
    named = set()
    for script in glob.glob(os.path.join(REPO, "ci", "*.sh")):
        with open(script) as fh:
            named |= set(re.findall(r"tests/[\w/]*\.py(?:::[\w\[\]-]+)*", fh.read()))
    assert named, "the CI scripts name no test file"
    for node in sorted(named):
        path, *parts = node.split("::")
        body = ast.parse(_read(path)).body
        for part in parts:
            part = part.split("[")[0]
            found = [
                n for n in body
                if isinstance(n, (ast.ClassDef, ast.FunctionDef)) and n.name == part
            ]
            assert found, f"{node}: no {part!r}"
            body = found[0].body


def test_the_two_peak_tables_agree_on_the_v5e():
    """``benchmark/peaks.json`` (the benchmark's MFU and rooflines) and
    ``fedml_tpu.constants`` (``fedml-tpu perf``): two tables, one chip."""
    row = json.loads(_read("benchmark", "peaks.json"))["chips"]["TPU v5 lite"]
    assert constants.peak_bf16_flops("TPU v5 lite") == row["bf16_flops_per_s"]
    assert constants.hbm_bandwidth_bytes("TPU v5 lite") == row["hbm_bytes_per_s"]
