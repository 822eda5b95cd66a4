"""Where a round's time goes (ISSUE 26): the scopes inside the round
executable, the host phase spans that tile the round loops, and the
three outside sources of a stall (gc, compile, steal).

CPU, tiny sizes: names, nesting and tiling are checked here; what a
scope or a span *reads* is the chip's to say (benchmark/layer_metrics).
"""

import contextlib
import gc

import jax
import numpy as np
import pytest

import fedml_tpu
from fedml_tpu import models
from fedml_tpu.core import sample_store, sys_stats
from fedml_tpu.core.round_pipeline import RoundPipeline
from fedml_tpu.core.telemetry import Telemetry
from fedml_tpu.core.tracking import ProfilerEvent
from fedml_tpu.data import load
from fedml_tpu.simulation import FedAvgAPI
from fedml_tpu.simulation.fedavg_api import build_round_fn

ROUND_CHILDREN = (
    "round.prep", "round.dispatch", "round.wait", "eval", "flush.fetch",
    "flush.report", "round.ckpt",
)
TABLE = ("train.plan", "round", "train.drain") + ROUND_CHILDREN


def _build(make, depth=1, **kw):
    base = dict(
        dataset="mnist", synthetic_train_size=1200, synthetic_test_size=120,
        model="lr", partition_method="hetero", client_num_in_total=6,
        client_num_per_round=4, comm_round=4, epochs=4, batch_size=10,
        learning_rate=0.1, frequency_of_the_test=2, shuffle=False,
        pipeline_depth=depth,
    )
    base.update(kw)
    args = fedml_tpu.init(make(**base))
    ds = load(args)
    return args, FedAvgAPI(args, None, ds, models.create(args, ds.class_num))


def _spans(events):
    """Closed B/E pairs of one event list as (name, t0, t1, begin
    args, end args), in order of their ends."""
    open_, out = {}, []
    for e in events:
        if e["ph"] == "B":
            open_.setdefault(e["name"], []).append(e)
        elif e["ph"] == "E" and open_.get(e["name"]):
            b = open_[e["name"]].pop()
            out.append((e["name"], b["ts"], e["ts"], b.get("args", {}), e.get("args", {})))
    return out


# -- scopes inside the round executable ---------------------------------

def _round_case():
    from fedml_tpu.analysis.compiled import AuditContext
    from fedml_tpu.core.aggregation import weighted_average

    ctx = AuditContext(cohort_buckets=(4,))

    def aggregate(global_params, server_state, stacked, weights, cohort, rng):
        return weighted_average(stacked, weights), server_state

    def make():
        # as _build_jitted builds it: the audit's samples are flat, so
        # there is no shape to restore after the gather
        return jax.jit(build_round_fn(
            ctx.local_train_fn(), aggregate,
            sample_shape=sample_store.sample_shape(ctx.abstract_batches(8))))

    return ctx, make


@pytest.mark.parametrize(
    "scope", ["fed.gather", "fed.local_train", "fed.aggregate", "fwd_bwd", "opt"])
def test_scope_is_in_the_lowered_round(scope):
    ctx, make = _round_case()
    text = make().lower(
        ctx.abstract_params(), (), ctx.abstract_batches(8), ctx.sds((8,)),
        ctx.sds((4,), "int32"), ctx.abstract_key(), valid=ctx.sds((4,)),
    ).as_text(debug_info=True)
    assert scope in text
    if scope.startswith("fed."):
        # a component of the op names, under the jit's own
        assert f"jit(round_fn)/{scope}/" in text or f"/{scope}/" in text


def test_scopes_change_no_output_bit(monkeypatch):
    """named_scope is metadata: with every scope taken out the round
    returns the same bits."""
    ctx, make = _round_case()
    rng = np.random.default_rng(0)
    params = jax.tree.map(
        lambda s: jax.numpy.asarray(rng.normal(size=s.shape), s.dtype), ctx.abstract_params())
    b = ctx.abstract_batches(8)
    packed = type(b)(
        x=jax.numpy.asarray(rng.normal(size=b.x.shape), b.x.dtype),
        y=jax.numpy.asarray(rng.integers(0, ctx.class_num, b.y.shape), b.y.dtype),
        mask=jax.numpy.asarray(rng.integers(0, 2, b.mask.shape), b.mask.dtype))
    call = (params, (), packed, jax.numpy.arange(1.0, 9.0),
            jax.numpy.asarray([5, 1, 6, 1], "int32"), jax.random.PRNGKey(3))
    valid = jax.numpy.asarray([1.0, 1.0, 1.0, 0.0])
    with_scopes = make()(*call, valid=valid)
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    without = make()
    assert "fed.gather" not in without.lower(*call, valid=valid).as_text(debug_info=True)
    for a, b2 in zip(jax.tree.leaves(with_scopes), jax.tree.leaves(without(*call, valid=valid))):
        assert np.array_equal(np.asarray(a), np.asarray(b2))


def test_scopes_survive_the_compile_cache_settings(tmp_path, args_factory, compile_cache_reset):
    """Enabling the persistent cache makes op metadata part of its key
    and cuts the traceback in MLIR locations to the op's own frame;
    the compiled round must still name every scope (turning full
    tracebacks off instead drops the name stack inside loop bodies:
    on the chip ``fed.local_train`` vanished from the trace)."""
    import re

    from fedml_tpu.core import compile_cache

    assert compile_cache.maybe_enable_compile_cache(
        args_factory(compile_cache_dir=str(tmp_path / "xla")))
    assert jax.config.jax_compilation_cache_include_metadata_in_key
    ctx, make = _round_case()
    compiled = make().lower(
        ctx.abstract_params(), (), ctx.abstract_batches(8), ctx.sds((8,)),
        ctx.sds((4,), "int32"), ctx.abstract_key(), valid=ctx.sds((4,)),
    ).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', compiled)
    for scope in ("fed.gather", "fed.local_train", "fed.aggregate", "fwd_bwd", "opt"):
        assert sum(scope in n.split("/") for n in op_names) >= 3, scope
    # the caller's frames are no part of a location any more
    assert "test_round_spans.py" not in make().lower(
        ctx.abstract_params(), (), ctx.abstract_batches(8), ctx.sds((8,)),
        ctx.sds((4,), "int32"), ctx.abstract_key(), valid=ctx.sds((4,)),
    ).as_text(debug_info=True)


# -- host phase spans that tile the round loop --------------------------

@pytest.mark.parametrize("depth", [1, 4])
@pytest.mark.parametrize("variant", ["plain", "eval_every_round", "checkpoint"])
def test_phase_spans_tile_the_pipeline(args_factory, tmp_path, monkeypatch, depth, variant):
    # rounds long enough (~0.1 s) that what lies between the children
    # -- a dozen span edges and one /proc/stat read, ~0.4 ms -- is
    # under the 1% it is held to; on the chip a round is 1.8 s
    kw = {"frequency_of_the_test": 1} if variant == "eval_every_round" else {}
    args, api = _build(args_factory, depth=depth, epochs=32, **kw)
    api.train()  # builds every executable and host program
    if variant == "checkpoint":
        args.checkpoint_dir, args.checkpoint_freq = str(tmp_path / "ckpt"), 2
    rec = api.telemetry.recorder
    run = RoundPipeline.run

    def timed_run(self, *a, **k):
        rec.instant("test.run_start")
        try:
            return run(self, *a, **k)
        finally:
            rec.instant("test.run_end")

    monkeypatch.setattr(RoundPipeline, "run", timed_run)
    # names and nesting hold on every call; the shares are clock
    # readings on a machine the other test workers share, so the best
    # of three calls is held to them
    shortfalls = []
    for attempt in range(3):
        if variant == "checkpoint" and attempt:
            import shutil

            shutil.rmtree(args.checkpoint_dir)  # start from round 0 again
        n0 = len(rec.tail(rec.capacity))
        api.train()
        shortfalls.append(_check_tiling(rec.tail(rec.capacity)[n0:], depth, variant))
        if not shortfalls[-1]:
            break
    assert not shortfalls[-1], shortfalls


def _check_tiling(events, depth, variant):
    """Asserts what must hold of any call; returns what fell short of
    the shares (empty when all held)."""
    short = []
    marks = {e["name"]: e["ts"] for e in events if e["name"].startswith("test.run_")}
    spans = _spans(events)
    by_name = {}
    for s in spans:
        by_name.setdefault(s[0], []).append(s)

    rounds = by_name["round"]
    assert [s[3]["round"] for s in rounds] == list(range(4))
    assert "round.ckpt" in by_name if variant == "checkpoint" else "round.ckpt" not in by_name
    dark_total = 0.0
    for _, r0, r1, _, _ in rounds:
        kids = [s for s in spans if s[0] in ROUND_CHILDREN and r0 <= s[1] and s[2] <= r1]
        # properly nested: two spans are disjoint or one holds the other
        for a in kids:
            for b in kids:
                assert a[2] <= b[1] or b[2] <= a[1] or (a[1] <= b[1] and b[2] <= a[2]) \
                    or (b[1] <= a[1] and a[2] <= b[2]), (a, b)
        top = [a for a in kids if not any(
            b is not a and b[1] <= a[1] and a[2] <= b[2] for b in kids)]
        assert {"round.prep", "round.dispatch", "round.wait"} <= {a[0] for a in top}
        dark = (r1 - r0) - sum(a[2] - a[1] for a in top)
        dark_total += dark
        # a round that waits (every round at depth 1) is ~0.1 s here; at
        # depth 4 one that finds the queue short is ~0.7 ms of host work
        # on the CPU, a quarter of it the span edges themselves: each
        # round's dark part is bounded, the 99% is over the call's rounds
        if dark >= 1000.0 or (depth == 1 and dark > 0.01 * (r1 - r0)):
            short.append(("round", dark, r1 - r0, [a[0] for a in top]))
    if dark_total > 0.01 * sum(r[2] - r[1] for r in rounds):
        short.append(("rounds", dark_total))
    # train.plan + the rounds + train.drain tile RoundPipeline.run
    (plan,), (drain,) = by_name["train.plan"], by_name["train.drain"]
    assert marks["test.run_start"] <= plan[1] and drain[2] <= marks["test.run_end"]
    tiled = (plan[2] - plan[1]) + sum(r[2] - r[1] for r in rounds) + (drain[2] - drain[1])
    if tiled < 0.99 * (marks["test.run_end"] - marks["test.run_start"]):
        short.append(("run", tiled, marks["test.run_end"] - marks["test.run_start"]))
    # what stays where and what it was
    assert sum(e["name"] == "pipeline.dispatch" for e in events) == 4
    for e in events:
        if e["name"] == "pipeline.dispatch":
            assert any(r[1] <= e["ts"] <= r[2] and r[3]["round"] == e["args"]["round"]
                       for r in rounds)
    return short


def test_round_end_carries_steal_ticks_and_the_histogram_fills(args_factory):
    _, api = _build(args_factory, comm_round=3)
    api.train()
    rounds = [s for s in _spans(api.telemetry.recorder.tail(10 ** 6)) if s[0] == "round"]
    assert len(rounds) == 3
    if sys_stats.cpu_steal_ticks() is not None:
        assert all(isinstance(r[4]["steal_ticks"], int) and r[4]["steal_ticks"] >= 0
                   for r in rounds)
    hists = api.telemetry.snapshot()["histograms"]
    assert hists["span_seconds{name=round}"]["count"] == 3
    assert hists["span_seconds{name=round.wait}"]["count"] == 3
    assert 'span_seconds_count{name="train.plan"' in api.telemetry.prometheus_text()
    # the per-span list that grew for the life of the process is gone
    assert not hasattr(api.profiler, "spans")
    assert api.profiler.summary()["round"]["count"] == 3


@pytest.mark.parametrize("depth", [1, 4])
def test_host_syncs_identical_with_telemetry_off(args_factory, depth):
    stats = {}
    for enabled in (True, False):
        Telemetry.reset()
        _, api = _build(args_factory, depth=depth, comm_round=6, telemetry=enabled)
        api.train()
        stats[enabled] = api.pipeline_stats
        if not enabled:
            assert len(api.telemetry.recorder) == 0
    assert stats[True]["host_syncs"] == stats[False]["host_syncs"]
    assert stats[True]["host_syncs_per_round"] == stats[False]["host_syncs_per_round"]


def test_sync_loop_uses_the_same_names(args_factory):
    _, api = _build(args_factory, comm_round=2, frequency_of_the_test=1)
    api._keep_stacked = False
    api.mode = "sequential"
    api.train()
    names = {s[0] for s in _spans(api.telemetry.recorder.tail(10 ** 6))}
    assert {"round", "round.prep", "round.dispatch", "eval", "flush.report"} <= names
    assert not names & {"round.wait", "flush.fetch", "train.plan"}


# -- the three outside sources of a stall -------------------------------

def test_forced_collection_leaves_a_gc_span(args_factory):
    _, api = _build(args_factory, comm_round=2, frequency_of_the_test=1)
    api.metrics_reporter.add_sink(lambda rec: gc.collect())
    before = len(gc.callbacks)
    api.train()
    assert len(gc.callbacks) == before  # the watch ends with train()
    spans = _spans(api.telemetry.recorder.tail(10 ** 6))
    full = [s for s in spans if s[0] == "gc" and s[3].get("generation") == 2]
    assert len(full) >= 2 and all("collected" in s[3] for s in full)
    reports = [s for s in spans if s[0] == "flush.report"]
    # the two forced ones ran inside report spans (the collector may
    # add full collections of its own anywhere)
    assert sum(any(r[1] <= g[1] and g[2] <= r[2] for r in reports) for g in full) >= 2
    assert api.profiler.summary()["gc"]["count"] >= 2


def test_first_train_leaves_compile_events_inside_spans(args_factory):
    _, api = _build(args_factory, comm_round=2)
    api.train()
    events = api.telemetry.recorder.tail(10 ** 6)
    compiles = [e for e in events if e["name"] == "compile"]
    assert compiles and all(e["args"]["seconds"] >= 0 for e in compiles)
    spans = [s for s in _spans(events) if s[0] in TABLE]
    in_train = [e for e in compiles if any(s[1] <= e["ts"] <= s[2] for s in spans)]
    # the round executable and evaluation are built inside train();
    # what init and the constructor built came before any span
    assert len(in_train) >= 2
    first_plan = min(s[1] for s in spans if s[0] == "train.plan")
    assert all(e in in_train for e in compiles if e["ts"] >= first_plan)


def test_gc_callback_takes_no_lock():
    """A collection can start while the recorder's lock is held; the
    callback must not wait for it."""
    tel = Telemetry.get_instance()
    prof = ProfilerEvent()
    tel.attach_profiler(prof)
    with prof.watch_stalls():
        with tel.recorder._lock, tel._lock:
            gc.collect()
        assert not [e for e in tel.recorder.tail() if e["name"] == "gc"]
        with prof.span("after"):
            pass
    names = [(e["name"], e["ph"]) for e in tel.recorder.tail()]
    assert names == [("after", "B"), ("gc", "B"), ("gc", "E"), ("after", "E")]
    ts = [e["ts"] for e in tel.recorder.tail()]
    assert ts[1] <= ts[2] <= ts[0]  # the collection ran before the span began


def test_steal_ticks_reads_proc_stat(tmp_path, monkeypatch):
    import builtins

    real_open = builtins.open
    fake = tmp_path / "stat"
    fake.write_bytes(b"cpu  10 0 20 3000 5 0 1 42 0 0\ncpu0 1 2 3\n")
    monkeypatch.setattr(
        builtins, "open",
        lambda p, *a, **k: real_open(fake if p == "/proc/stat" else p, *a, **k))
    assert sys_stats.cpu_steal_ticks() == 42
    fake.write_bytes(b"cpu  10 0\n")
    assert sys_stats.cpu_steal_ticks() is None


def test_devtime_opens_no_named_scope():
    """A scope round the call of a jitted function reaches no HLO."""
    from fedml_tpu.core import devtime

    assert not hasattr(devtime, "_named_scope")
    f = jax.jit(lambda x: x + 1)
    with devtime.measure("simulation.round_fn", bucket="b4"):
        text = f.lower(1.0).as_text(debug_info=True)
    assert "exec." not in text


# -- the same primitive in DistributedTrainer.run() ---------------------

def test_distributed_epochs_are_tiled_by_phase_spans(args_factory):
    from fedml_tpu import data
    from fedml_tpu.distributed import DistributedTrainer

    args = fedml_tpu.init(args_factory(
        training_type="distributed", dataset="shakespeare", synthetic_train_size=32,
        synthetic_test_size=8, model="transformer", vocab_size=32, seq_len=8, num_layers=1,
        num_heads=2, embed_dim=16, client_num_in_total=1, client_num_per_round=1, comm_round=1,
        epochs=3, batch_size=8, learning_rate=0.1, frequency_of_the_test=2,
        mesh_shape={"dp": 1}, run_id="epoch_spans"))
    ds = data.load(args)
    trainer = DistributedTrainer(args, None, ds, models.create(args, ds.class_num))
    trainer.run()
    spans = _spans(Telemetry.get_instance().recorder.tail(10 ** 6))
    epochs = [s for s in spans if s[0] == "epoch"]
    assert [s[3]["epoch"] for s in epochs] == [0, 1, 2]
    if sys_stats.cpu_steal_ticks() is not None:
        assert all("steal_ticks" in s[4] for s in epochs)
    always = ["epoch.place", "epoch.dispatch", "epoch.wait", "epoch.fetch", "epoch.report"]
    for i, (_, e0, e1, _, _) in enumerate(epochs):
        kids = [s for s in spans if s[0].startswith("epoch.") and e0 <= s[1] and s[2] <= e1]
        # evaluation on every second epoch and on the last; no checkpoint asked for
        want = always[:4] + (["epoch.eval"] if i in (1, 2) else []) + always[4:]
        assert [s[0] for s in sorted(kids, key=lambda s: s[1])] == want
        assert all(a[2] <= b[1] for a, b in zip(sorted(kids, key=lambda s: s[1]),
                                                sorted(kids, key=lambda s: s[1])[1:]))
    # the first epoch compiles: the events lie inside its dispatch (and the first evaluation)
    compiles = [e for e in Telemetry.get_instance().recorder.tail(10 ** 6) if e["name"] == "compile"]
    inside = [s for s in spans if s[0] in ("epoch.dispatch", "epoch.eval", "epoch.place")]
    assert any(s[1] <= e["ts"] <= s[2] for e in compiles for s in inside)
