"""A later PR adds a configuration, a traffic mix and a per-layer metric
as new files plus ``BENCHMARK.json`` entries -- no file that is there is
edited. Shown by adding one of each in a temporary directory."""

import json
import os

from conftest import TINY_DIR, run_cell, tiny_spec


def test_add_config_workload_and_metric_as_files(tmp_path, monkeypatch):
    extra = tmp_path / "extra"
    for kind in ("configs", "workloads", "layer_metrics"):
        (extra / kind).mkdir(parents=True)
    # a configuration: its file of sizes (here the tiny LM, one layer deeper)
    with open(os.path.join(TINY_DIR, "configs", "tiny_lm.json")) as f:
        cfg = json.load(f)
    cfg["model"]["n_layer"] = 3
    cfg["program_args"]["num_layers"] = 3
    (extra / "configs" / "deeper_lm.json").write_text(json.dumps(cfg))
    # a traffic mix: parameters only, read by the family's one generator
    (extra / "workloads" / "short_epochs.json").write_text(json.dumps({
        "what": "epochs of 2 steps, 3 epochs a call", "steps_per_epoch": 2,
        "epochs_per_call": 3, "eval_batches": 1, "trace_seconds": 1, "host_spans": []}))
    # a per-layer metric: a small reader of its own
    (extra / "layer_metrics" / "epochs_per_call_seen.py").write_text(
        "def read(ctx):\n"
        "    win = ctx['window']\n"
        "    return win['epochs'] / win['calls'] if win.get('calls') else None\n")
    spec = tiny_spec()
    spec["paths"] = [str(extra)] + spec["paths"]
    spec["configs"].append({
        "name": "deeper_lm", "source": "tests", "reduced": [], "why": "added as a file",
        "file": str(extra / "configs" / "deeper_lm.json")})
    spec["workloads"].append({
        "name": "deeper_lm.short_epochs", "config": "deeper_lm", "traffic": "short_epochs",
        "chips": 1, "why": "added as a file"})
    spec["per_layer"].append({
        "name": "epochs_per_call_seen", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "LM trainer", "moves": "tokens_per_s",
        "workloads": ["deeper_lm.short_epochs"]})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "tiny_e3" in m.get("workloads", []):
            m["workloads"].append("deeper_lm.short_epochs")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell, res = run_cell(
        "deeper_lm.short_epochs", str(tmp_path), monkeypatch, seed=5, seconds=0.2, trace=True)
    assert cell.config["model"]["n_layer"] == 3 and cell.traffic["epochs_per_call"] == 3
    assert res["correct"] is True, res["compared"]
    assert res["metrics"]["epochs_per_call_seen"] == {"value": 3.0, "unit": "count"}
    assert res["attempted"] % (2 * 3) == 0  # whole calls of 3 epochs of 2 steps
    assert "mfu_pct.lm" in res["metrics"]   # the metrics that were there still read
