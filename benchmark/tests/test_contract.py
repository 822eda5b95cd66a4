"""``BENCHMARK.json`` against the driver's contract, before any run."""

import json
import os
import re

import pytest

import harness
from conftest import CHECKOUT, full_spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$|head_size|expansion|per_tok)")


@pytest.fixture(scope="module", params=["committed", "with_left_out"])
def spec(request, tmp_path_factory):
    """``BENCHMARK.json`` as committed, and as it stands once the cells
    under ``left_out/`` are added back: both keep the contract."""
    path = os.path.join(CHECKOUT, "BENCHMARK.json")
    if request.param == "with_left_out":
        path = str(tmp_path_factory.mktemp("full") / "BENCHMARK.json")
        with open(path, "w") as f:
            json.dump(full_spec(), f, indent=1)
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        return json.load(f)


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level(spec):
    assert sorted(spec) == sorted(
        ["command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"])
    assert 1 <= len(spec["paths"]) <= 16 and all(PATH.match(p) for p in spec["paths"])
    assert not any(p.startswith("/") or ".." in p.split("/") for p in spec["paths"])
    assert 1 <= len(spec["command"]) <= 32 and all(_line(w) for w in spec["command"])
    assert isinstance(spec["run_seconds"], int) and 1 <= spec["run_seconds"] <= 51
    # a full check with all 24 cells fits the driver's 43200 s
    rs = spec["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs(spec):
    assert 1 <= len(spec["configs"]) <= 24
    names = [c["name"] for c in spec["configs"]]
    files = [c["file"] for c in spec["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in spec["workloads"]}
    for c in spec["configs"]:
        assert sorted(c) == ["file", "name", "reduced", "source", "why"]
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in spec["paths"])
        assert os.path.isfile(os.path.join(CHECKOUT, c["file"]))
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) and not WIDTHS.search(k) for k in c["reduced"])
        assert c["name"] in used
        with open(os.path.join(CHECKOUT, c["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == c["reduced"]
        for key in ("family", "reference", "source", "deployment", "assumed", "limits", "precision"):
            assert key in body, (c["name"], key)


def _cell(spec, name, tmp_path):
    """``harness.Cell`` over this spec: its relative paths are the
    checkout's."""
    spec = json.loads(json.dumps(spec))
    spec["paths"] = [os.path.join(CHECKOUT, p) for p in spec["paths"]]
    for c in spec["configs"]:
        c["file"] = os.path.join(CHECKOUT, c["file"])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return harness.Cell(name, root=str(tmp_path))


def test_workloads(spec, tmp_path):
    cells = spec["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    configs = {c["name"] for c in spec["configs"]}
    for w in cells:
        assert sorted(w) == ["chips", "config", "name", "traffic", "why"]
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and _line(w["why"])
        cell = _cell(spec, w["name"], tmp_path)  # every file a name stands for is there
        assert cell.traffic and cell.family_module() and cell.flops_module()
        assert cell.module("reference", cell.config["reference"])
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)


def test_metrics(spec):
    e2e, layers = spec["end_to_end"], spec["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layers) <= 128
    names = [m["name"] for m in e2e + layers]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in spec["workloads"]}
    reports = {n: set() for n in cells}
    for m in e2e:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        for c in m.get("workloads", cells):
            assert c in cells
            reports[c].add(m["name"])
    setup = [m for m in e2e if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0] and setup[0]["bound"] == 0.1
    for c in cells:
        assert "setup_s" in reports[c] and len(reports[c]) >= 2
    e2e_names = {m["name"] for m in e2e}
    layered = set()
    for m in layers:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and _line(m["layer"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert m["moves"] in e2e_names
        for c in m.get("workloads", cells):
            assert m["moves"] in reports[c], (m["name"], c)
            layered.add(c)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
        # its reader is a file of its own, found by the metric's name
        assert os.path.isfile(os.path.join(CHECKOUT, "benchmark", "layer_metrics", m["name"] + ".py"))
    assert layered == cells


def test_kernel_rooflines_stand_beside_a_whole_step_mfu(spec):
    layers = spec["per_layer"]
    for m in layers:
        if m["name"].endswith("_roofline"):
            assert any(
                "mfu" in o["name"] and o["moves"] == m["moves"]
                and set(m["workloads"]) <= set(o["workloads"]) for o in layers)


def test_every_file_under_paths_is_named_from_a_names_characters(spec):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in spec["paths"]:
        for root, dirs, files in os.walk(os.path.join(CHECKOUT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                assert ok.match(os.path.relpath(os.path.join(root, f), CHECKOUT))
