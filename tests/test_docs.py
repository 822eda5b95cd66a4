"""docs/ stays truthful: the configuration page is generated from the
arguments schema and must match the checked-in copy, and the
hand-written pages may only reference knobs/files that exist."""

import importlib.util
import os
import re

import pytest

pytestmark = pytest.mark.smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = os.path.join(REPO, "docs")


def _gen_module():
    spec = importlib.util.spec_from_file_location(
        "gen_config_docs", os.path.join(REPO, "scripts", "gen_config_docs.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_configuration_md_is_fresh():
    mod = _gen_module()
    generated = mod.render(mod.extract_entries())
    with open(os.path.join(DOCS, "configuration.md")) as f:
        assert f.read() == generated, (
            "docs/configuration.md is stale; run scripts/gen_config_docs.py"
        )


def test_every_default_knob_documented():
    from fedml_tpu.arguments import _DEFAULTS

    with open(os.path.join(DOCS, "configuration.md")) as f:
        text = f.read()
    missing = [k for k in _DEFAULTS if f"`{k}`" not in text]
    assert not missing, f"knobs missing from configuration.md: {missing}"


def test_index_links_resolve():
    with open(os.path.join(DOCS, "index.md")) as f:
        text = f.read()
    for target in re.findall(r"\]\((\w+\.md)\)", text):
        assert os.path.isfile(os.path.join(DOCS, target)), target


def test_docs_mention_only_real_knobs():
    """Backticked snake_case tokens that look like config knobs must
    exist in the schema (or be known non-knob identifiers) — stale docs
    are worse than no docs."""
    from fedml_tpu.arguments import _DEFAULTS

    known = set(_DEFAULTS) | {
        # non-knob identifiers the pages legitimately mention
        "run_simulation", "single_process", "cross_silo", "cross_device",
        "group_num", "group_comm_round", "client_trainer",
        "server_aggregator", "run_server", "run_client", "drop_prob",
        "delay_s", "checkpoint_freq", "synthetic_train_size",
        "synthetic_test_size", "input_dim", "output_dim", "hidden_dim",
        "num_layers", "num_heads", "embed_dim", "seq_len", "vocab_size",
        "max_len", "num_experts", "capacity_factor", "moe_every",
        "attn_fn", "loss_fn", "metrics_from_sums", "example_shape",
        "fed_cifar100", "fed_emnist", "fed_shakespeare",
        "stackoverflow_nwp", "stackoverflow_lr", "fashion_mnist",
        "data_batch", "fedml_tpu", "mnist", "vs_baseline",
        "mfu_vs_bf16_peak", "tag_count",
        "word_count", "materialize_real_digits", "jax", "shard_map",
        "ppermute", "vmap",
    }
    offenders = []
    for page in os.listdir(DOCS):
        if not page.endswith(".md") or page == "configuration.md":
            continue
        with open(os.path.join(DOCS, page)) as f:
            text = f.read()
        for tok in re.findall(r"`([a-z][a-z0-9_]*_[a-z0-9_]+):", text):
            if tok not in known:
                offenders.append((page, tok))
    assert not offenders, f"docs reference unknown knobs: {offenders}"
