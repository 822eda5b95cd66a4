"""The scope and gap reduction (``layer_metrics/_scopes.py``) and the six
readers on it: on hand-made planes, on serialized planes for the two
sources ``ProfileData`` does not hand out, and on a one-round trace
recorded on the v5e (``testdata/fedavg_scopes_round.xplane.pb.gz``: a
``train()`` of one round and its evaluation from this PR's tree, cut to
the two device lines, each operation's ``tf_op`` and program id, the
program's host spans and the embedded HLO's instruction and op names;
``tools/probe_scopes.py --strip`` made it)."""

import glob
import gzip
import os
import sys
from types import SimpleNamespace as NS

import pytest

from conftest import BENCH_DIR, run_cell

sys.path.insert(0, os.path.join(BENCH_DIR, "layer_metrics"))
import _scopes  # noqa: E402
import _xplane_wire as wire  # noqa: E402
import harness  # noqa: E402

MS = 1_000_000
RECORDED = os.path.join(BENCH_DIR, "testdata", "fedavg_scopes_round.xplane.pb.gz")
ROUND = "jit(round_fn)/fed.local_train/vmap()/while/body/closed_call/while/body/closed_call"


def ev(name, s, e, **stats):
    return NS(name=name, start_ns=s, duration_ns=e - s, stats=list(stats.items()))


def plane(name, **lines):
    return NS(name=name, stats=[], lines=[NS(name=k, events=v) for k, v in lines.items()])


def chip(index, shift=0, tagged=True):
    """One round on one chip: a gather loop, a training loop with a
    nested loop inside, an untagged copy, the aggregation."""
    tag = (lambda s: {"tf_op": s}) if tagged else (lambda s: {})
    t = lambda ms: (ms + shift) * MS  # noqa: E731
    return plane(f"/device:TPU:{index}", **{
        "XLA Modules": [ev("jit_round_fn(7)", t(0), t(100)), ev("jit_eval_all(9)", t(110), t(130))],
        "XLA Ops": [
            ev("%while.1 = (...) while(...)", t(0), t(30), **tag("jit(round_fn)/fed.gather/while:")),
            ev("%dus.3 = bf16[1] dynamic-update-slice(...)", t(1), t(29),
               **tag("jit(round_fn)/fed.gather/jit(_take)/gather:")),
            ev("%while.2 = (...) while(...)", t(30), t(90), **tag("jit(round_fn)/fed.local_train/vmap()/while:")),
            ev("%while.5 = (...) while(...)", t(31), t(89), **tag(ROUND + ":")),
            ev("%fusion.3 = f32[8] fusion(...)", t(32), t(60), **tag(ROUND + "/fwd_bwd/jvp()/mul:")),
            ev("%fusion.4 = f32[8] fusion(...)", t(60), t(88), **tag("opt/mul:")),
            ev("%copy.1 = f32[8] copy(...)", t(90), t(92)),
            ev("%fusion.9 = f32[8] fusion(...)", t(94), t(100), **tag("jit(round_fn)/fed.aggregate/mul:")),
            ev("%fusion.3 = f32[8] fusion(...)", t(110), t(130), **tag("jit(eval_all)/vmap()/mul:")),
        ]})


def test_components_not_substrings():
    assert _scopes.scopes_in(ROUND + "/opt/jit(_where)/select_n:") == ("fed.local_train", "opt")
    assert _scopes.scopes_in("fwd_bwd/transpose(jvp(ResNet))/Dense_0/reduce_sum") == ("fwd_bwd",)
    assert _scopes.innermost_scope("jit(round_fn)/fed.gather/jit(_take)/gather:") == "fed.gather"
    assert _scopes.scopes_in("jit(round_fn)/jit(_take)/gather:") == ()
    assert _scopes.scopes_in("jit(f)/optimizer/fed.gathering/adopt") == ()
    assert _scopes.scopes_in("") == () and _scopes.innermost_scope(None) is None


def test_union_of_nested_loops_not_their_sum():
    s = _scopes.reduce_scopes(NS(planes=[chip(0)]))
    assert s["source"] == "event_stat" and s["why"] is None and s["devices"] == 1
    assert s["scope_s"]["fed.gather"] == pytest.approx(0.030)       # the loop holds its body: 30, not 58
    assert s["scope_s"]["fed.local_train"] == pytest.approx(0.060)  # two loops and their bodies: 60, not 174
    assert s["scope_s"]["fwd_bwd"] == pytest.approx(0.028)
    assert s["scope_s"]["opt"] == pytest.approx(0.028)              # found by its own component alone
    assert s["scope_s"]["fed.aggregate"] == pytest.approx(0.006)
    # the copy is no scope's, and evaluation's operations are nobody's business here
    assert s["untagged_s"] == pytest.approx(0.002)
    assert sum(s["scope_s"][k] for k in ("fed.gather", "fed.local_train", "fed.aggregate")) \
        == pytest.approx(0.100 - 0.002 - 0.002)


def test_two_chips_are_averaged_not_added():
    one = _scopes.reduce_scopes(NS(planes=[chip(0)]))
    two = _scopes.reduce_scopes(NS(planes=[chip(1, shift=7), chip(0)]))
    assert two["devices"] == 2
    assert two["scope_s"] == pytest.approx(one["scope_s"])
    assert two["untagged_s"] == pytest.approx(one["untagged_s"])


def test_no_scope_anywhere_reads_none_and_says_why(capsys):
    s = _scopes.reduce_scopes(NS(planes=[chip(0, tagged=False)]))
    assert s["source"] is None and s["scope_s"] == {}
    assert "before the scopes existed" in s["why"]
    ctx = {"_scopes": s, "trace": {"modules": {"jit_round_fn": {"count": 1.0, "total_s": 0.1}}}}
    for scope in ("fed.gather", "fed.local_train", "fed.aggregate"):
        assert _scopes.scope_ms_per_round(ctx, scope) is None
    host_only = _scopes.reduce_scopes(NS(planes=[plane("/host:CPU", main=[ev("round", 0, 5)])]))
    assert host_only["source"] is None and "no device plane" in host_only["why"]


def _serialized(tf_op_on="metadata"):
    from jax.profiler import ProfileData

    own = 'stats { metadata_id: 1 str_value: "jit(round_fn)/fed.gather/while:" }'
    text = '''
planes { name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 10 offset_ps: 0 duration_ps: 100000000000 } }
  lines { name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 30000000000 %s }
    events { metadata_id: 2 offset_ps: 30000000000 duration_ps: 60000000000 }
    events { metadata_id: 3 offset_ps: 90000000000 duration_ps: 10000000000 } }
  event_metadata { key: 10 value { id: 10 name: "jit_round_fn(7)" } }
  event_metadata { key: 1 value { id: 1 name: "%%while.1 = (...) while(...)"
    stats { metadata_id: 2 uint64_value: 7 } %s
    stats { metadata_id: 3 str_value: "/opt/venv/lib/python3.12/site-packages/flax/linen/linear.py:1" } } }
  event_metadata { key: 2 value { id: 2 name: "%%while.2 = (...) while(...)"
    stats { metadata_id: 2 uint64_value: 7 } %s } }
  event_metadata { key: 3 value { id: 3 name: "%%fusion.9 = f32[8] fusion(...)"
    stats { metadata_id: 2 uint64_value: 7 } } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "program_id" } }
  stat_metadata { key: 3 value { id: 3 name: "source_stack" } }
}''' % (
        own if tf_op_on == "event" else "",
        'stats { metadata_id: 1 str_value: "jit(round_fn)/fed.gather/while:" }'
        if tf_op_on == "metadata" else "",
        'stats { metadata_id: 1 str_value: "jit(round_fn)/fed.local_train/vmap()/while:" }'
        if tf_op_on == "metadata" else "")
    raw = ProfileData.text_proto_to_serialized_xspace(text)
    return ProfileData.from_serialized_xspace(raw), raw


def test_op_name_on_the_events_metadata_as_the_v5e_has_it():
    data, raw = _serialized("metadata")
    assert [v for _, v in next(iter(
        next(ln for ln in next(iter(data.planes)).lines if ln.name == "XLA Ops").events)).stats
        if isinstance(v, str)] == []  # ProfileData hands out the event's own statistics only
    s = _scopes.reduce_scopes(data, raw)
    assert s["source"] == "event_metadata"
    assert s["scope_s"] == pytest.approx({"fed.gather": 0.030, "fed.local_train": 0.060})
    assert s["untagged_s"] == pytest.approx(0.010)
    # without the file's bytes there is nothing to look the metadata up in
    assert _scopes.reduce_scopes(data)["source"] is None


def test_a_source_path_is_not_an_op_name():
    """``/opt/venv/...`` in ``source_stack`` has a component ``opt``;
    only the op-name statistics are searched (the first chip probe of
    this PR read 1.86 s of ``opt`` out of a trace that had no scope)."""
    data, raw = _serialized("nowhere")
    s = _scopes.reduce_scopes(data, raw)
    assert s["source"] is None and s["scope_s"] == {}


def test_op_name_on_the_event_itself():
    data, raw = _serialized("event")
    s = _scopes.reduce_scopes(data, raw)
    assert s["source"] == "event_stat" and s["scope_s"] == pytest.approx({"fed.gather": 0.030})


def test_op_name_from_the_embedded_hlo(tmp_path):
    """A CPU profile embeds the HLO as a TPU one does; its module's
    instruction names drive hand-made device events."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    def round_fn(x):
        with jax.named_scope("fed.gather"):
            y = jnp.take(x, jnp.arange(4), axis=0)
        with jax.named_scope("fed.aggregate"):
            return jnp.tanh(y).sum(0)

    f = jax.jit(round_fn)
    x = jnp.ones((8, 16))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    with open(glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0], "rb") as fh:
        cpu = fh.read()
    (module, names), = [m for m in wire.embedded_hlo(cpu) if m[0].startswith("jit_round_fn")]
    by_scope = {}
    for inst, op_name in names.items():
        by_scope.setdefault(_scopes.innermost_scope(op_name), []).append(inst)
    assert by_scope["fed.gather"] and by_scope["fed.aggregate"]
    device = ProfileData.text_proto_to_serialized_xspace('''
planes { name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 0 events { metadata_id: 10 offset_ps: 0 duration_ps: 9000000000 } }
  lines { name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000000 }
    events { metadata_id: 2 offset_ps: 2000000000 duration_ps: 5000000000 } }
  event_metadata { key: 10 value { id: 10 name: "%s" } }
  event_metadata { key: 1 value { id: 1 name: "%%%s = f32[4,16] op(...)" } }
  event_metadata { key: 2 value { id: 2 name: "%%%s = f32[16] op(...)" } }
}''' % (module, by_scope["fed.gather"][0], by_scope["fed.aggregate"][0]))
    raw = device + cpu  # serialized repeated fields concatenate
    s = _scopes.reduce_scopes(ProfileData.from_serialized_xspace(raw), raw)
    assert s["source"] == "embedded_hlo"
    assert s["scope_s"] == pytest.approx({"fed.gather": 0.002, "fed.aggregate": 0.005})


def test_gaps_go_to_the_innermost_span_around_them():
    dev = plane("/device:TPU:0", **{
        "XLA Modules": [ev("jit_round_fn(7)", 0, 100 * MS)],
        "XLA Ops": [ev("%a = x", 0, 10 * MS), ev("%b = x", 12 * MS, 40 * MS), ev("%c = x", 43 * MS, 60 * MS),
                    ev("%d = x", 64 * MS, 80 * MS), ev("%e = x", 85 * MS, 100 * MS)]})
    host = plane("/host:CPU", main=[
        ev("train.plan", 0, 5 * MS), ev("round", 8 * MS, 70 * MS), ev("round.wait", 9 * MS, 30 * MS),
        ev("gc", 10 * MS, 11500000), ev("flush.fetch", 40 * MS, 50 * MS),
        ev("not_the_programs", 60 * MS, 70 * MS), ev("train.drain", 90 * MS, 99 * MS)])
    s = _scopes.reduce_scopes(NS(planes=[host, dev]))
    # 10-12 in gc (inside round.wait inside round), 40-43 in flush.fetch,
    # 60-64 in round alone, 80-85 in nothing
    assert s["gap_s"] == pytest.approx(
        {"gc": 0.002, "flush.fetch": 0.003, "round": 0.004, "unnamed": 0.005})
    assert s["gap_total_s"] == pytest.approx(0.014)
    assert _scopes.idle_unnamed_pct({"_scopes": s}) == pytest.approx(100 * 5 / 14)
    assert _scopes.idle_unnamed_pct({"_scopes": dict(s, gap_s={}, gap_total_s=0.0)}) is None


# -- the readers, on a hand-made ctx -------------------------------------

def _event(name, ph, ts, tid=1, **args):
    return {"name": name, "ph": ph, "ts": ts, "tid": tid, "pid": 1, "cat": "profiler", "args": args}


def _window_events(with_children=True):
    out = [_event("round", "B", 5), _event("round", "E", 9),  # before the window
           _event("bench.window_start", "i", 10)]
    for i, (t0, wait, fetch) in enumerate([(100, 1800_000, 0), (2_000_000, 1750_000, 900_000)]):
        out.append(_event("round", "B", t0, round=i))
        t = t0 + 400
        if with_children:
            out += [_event("round.prep", "B", t0 + 10), _event("round.prep", "E", t0 + 300),
                    _event("round.dispatch", "B", t0 + 310), _event("round.dispatch", "E", t)]
            out += [_event("round.wait", "B", t), _event("round.wait", "E", t + wait)]
            t += wait
            if fetch:
                out += [_event("eval", "B", t + 100), _event("eval", "E", t + 600),
                        _event("flush.fetch", "B", t + 700), _event("flush.fetch", "E", t + 700 + fetch),
                        _event("gc", "B", t + 800), _event("gc", "E", t + 900)]
                t += 700 + fetch + 2500
        out.append(_event("round", "E", t + 100, steal_ticks=0))
    out.append(_event("bench.window_end", "i", 9_000_000))
    return out


def _reader(name):
    return harness.load_module(os.path.join(BENCH_DIR, "layer_metrics", name + ".py"))


def test_host_readers_take_the_window_and_leave_the_waits(monkeypatch):
    monkeypatch.setattr(_scopes, "program_events", _window_events)
    # round 0: 400 us of prep and dispatch + 100 us; round 1: 400 + 700 + 2500 + 100
    assert _scopes.round_host_ms(_window_events()) == pytest.approx([0.5, 3.7])
    ctx = {}
    assert _reader("round_host_ms").read(ctx) == pytest.approx(2.1)
    monkeypatch.setattr(_scopes, "program_events", list)  # read once a run
    assert _reader("round_host_max_ms").read(ctx) == pytest.approx(3.7)


def test_host_readers_read_nothing_of_a_program_without_the_children(monkeypatch):
    """At the parent commit ``round`` wrapped the dispatch alone."""
    monkeypatch.setattr(_scopes, "program_events", lambda: _window_events(with_children=False))
    for name in ("round_host_ms", "round_host_max_ms"):
        assert _reader(name).read({}) is None
    assert _scopes.round_host_ms([_event("round", "B", 1), _event("round", "E", 2)]) is None  # no window


def test_scope_readers_divide_by_the_rounds_the_trace_saw(monkeypatch):
    summary = {"source": "event_metadata", "why": None, "gap_s": {"round.wait": 0.03, "unnamed": 0.01},
               "gap_total_s": 0.04, "untagged_s": 0.0,
               "scope_s": {"fed.gather": 4.85, "fed.local_train": 13.16, "fed.aggregate": 0.09}}
    ctx = {"_scopes": summary, "trace": {"modules": {
        "jit_round_fn": {"count": 10.0, "total_s": 18.1}, "jit_eval_all": {"count": 4.0, "total_s": 1.9}}}}
    assert _reader("gather_device_ms").read(ctx) == pytest.approx(485.0)
    assert _reader("local_train_device_ms").read(ctx) == pytest.approx(1316.0)
    assert _reader("aggregate_device_ms").read(ctx) == pytest.approx(9.0)
    assert _reader("idle_unnamed_pct").read(ctx) == pytest.approx(25.0)
    ctx["trace"]["modules"] = {}
    assert _reader("gather_device_ms").read(ctx) is None


def test_the_trace_is_found_where_run_py_keeps_it(tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(_scopes, "TRACE_ROOT", str(tmp_path))
    cell = NS(name="some_cell")
    s = _scopes.summary({"cell": cell})
    assert s["source"] is None and "no trace under" in s["why"]
    assert "scopes none: no trace under" in capsys.readouterr().err
    d = tmp_path / "some_cell" / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    _, raw = _serialized("metadata")
    (d / "host.xplane.pb").write_bytes(raw)
    ctx = {"cell": cell}
    assert _scopes.summary(ctx)["source"] == "event_metadata"
    assert _scopes.summary(ctx) is ctx["_scopes"]  # once a run
    err = capsys.readouterr().err
    assert err.count("scopes {") == 1 and err.count("gaps {") == 1


# -- a whole traced line on the CPU --------------------------------------

def test_cpu_rehearsal_reports_the_spans_and_no_scope(tiny_root, narrow_resnet, monkeypatch, capsys):
    cell, res = run_cell("tiny_c4", tiny_root, monkeypatch, trace=True)
    metrics = res["metrics"]
    assert res["correct"] is True
    # the program's spans are there on any platform ...
    assert 0 < metrics["round_host_ms"]["value"] <= metrics["round_host_max_ms"]["value"]
    # ... a CPU trace has no device plane: no scope time, no gap, and it says so
    for name in ("gather_device_ms", "local_train_device_ms", "aggregate_device_ms", "idle_unnamed_pct"):
        assert name not in metrics
    assert "scopes none: the trace has no device plane" in capsys.readouterr().err


# -- the recorded round ---------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    with gzip.open(RECORDED) as f:
        raw = f.read()
    return ProfileData.from_serialized_xspace(raw), raw


def test_recorded_round_by_scope(recorded):
    data, raw = recorded
    s = _scopes.reduce_scopes(data, raw)
    assert s["source"] == "event_metadata" and s["devices"] == 1
    # one run of the round executable: 1,810.16 ms by its XLA Modules event
    import reduce_trace

    modules = reduce_trace.reduce_profile(data, host_spans=["round", "eval"])["modules"]
    round_s = modules["jit_round_fn"]["total_s"]
    assert modules["jit_round_fn"]["count"] == 1.0 and round_s == pytest.approx(1.810163993)
    assert s["scope_s"]["fed.gather"] == pytest.approx(0.487399334)
    assert s["scope_s"]["fed.local_train"] == pytest.approx(1.317994791)
    assert s["scope_s"]["fed.aggregate"] == pytest.approx(0.001882252)
    assert s["scope_s"]["fwd_bwd"] == pytest.approx(1.29680634)  # inside local training
    assert s["scope_s"]["opt"] == pytest.approx(0.000358352)
    three = sum(s["scope_s"][k] for k in ("fed.gather", "fed.local_train", "fed.aggregate"))
    assert three + s["untagged_s"] == pytest.approx(round_s, rel=1e-4)  # the scopes do not overlap
    assert abs(three - round_s) / round_s < 0.01                        # and leave under 1% dark
    # the loops themselves carry no op name on the v5e; their bodies do, and the
    # union over a body reaches what the loop's own event spans
    ops = {e.name.split(" = ")[0]: e.duration_ns for p in data.planes if p.name == "/device:TPU:0"
           for ln in p.lines if ln.name == "XLA Ops" for e in ln.events
           if e.name.startswith(("%while.144 ", "%while.145 "))}
    assert s["scope_s"]["fed.gather"] == pytest.approx(ops["%while.145"] / 1e9, rel=0.01)
    assert s["scope_s"]["fed.local_train"] == pytest.approx(ops["%while.144"] / 1e9, rel=0.01)
    ctx = {"_scopes": s, "trace": {"modules": modules}}
    assert _reader("gather_device_ms").read(ctx) == pytest.approx(487.399334)


def test_recorded_round_by_its_embedded_hlo(recorded, monkeypatch):
    """With no op name on the events' metadata the HLO the profile
    embeds gives the same scopes."""
    data, raw = recorded
    by_metadata = _scopes.reduce_scopes(data, raw)
    monkeypatch.setattr(_scopes, "OP_NAME_STATS", ())
    by_hlo = _scopes.reduce_scopes(data, raw)
    assert by_hlo["source"] == "embedded_hlo"
    for scope in ("fed.gather", "fed.local_train", "fed.aggregate", "fwd_bwd"):
        assert by_hlo["scope_s"][scope] == pytest.approx(by_metadata["scope_s"][scope], rel=2e-3)
    module, names = next(m for m in wire.embedded_hlo(raw) if m[0].startswith("jit_round_fn("))
    assert len(names) > 10_000
    assert {"fed.gather", "fed.local_train", "fed.aggregate", "fwd_bwd", "opt"} <= {
        _scopes.innermost_scope(v) for v in names.values()}


def test_recorded_round_gaps(recorded):
    data, raw = recorded
    s = _scopes.reduce_scopes(data, raw)
    # one traced train() of one round: the device idles while the call
    # plans, then between the round executable and evaluation
    assert s["gap_s"]["train.plan"] == pytest.approx(0.004359673)
    assert s["gap_s"]["round.wait"] == pytest.approx(0.001645113)
    assert "unnamed" not in s["gap_s"] and _scopes.idle_unnamed_pct({"_scopes": s}) == 0.0
    import reduce_trace

    old = dict(reduce_trace.reduce_profile(data, host_spans=["round", "eval"])["top_gaps"])
    # reduce_trace knows the traffic file's two names: the call's edge stays "no span" there
    assert old["no span"] == pytest.approx(s["gap_s"]["train.plan"])
    assert old["round"] == pytest.approx(s["gap_total_s"] - s["gap_s"]["train.plan"])
