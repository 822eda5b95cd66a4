"""The reduction from a profiler trace to numbers: on a trace recorded
on the v5e (one round and one evaluation of ``fedavg_r18_c32``, the
first 2.79 s of a window; statistics stripped, host plane cut to the
program's spans) and on hand-made planes for what that trace lacks
(several chips, gaps inside host spans)."""

import gzip
import os
from types import SimpleNamespace as NS

import pytest

import reduce_trace
from conftest import BENCH_DIR

RECORDED = os.path.join(BENCH_DIR, "testdata", "fedavg_round_eval.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    with gzip.open(RECORDED) as f:
        return ProfileData.from_serialized_xspace(f.read())


def test_recorded_trace(recorded):
    s = reduce_trace.reduce_profile(
        recorded, host_spans=["round", "eval"], kernel_names=["fusion.1075"])
    assert s["devices"] == 1
    # busy: checked against an independent sweep over the same events
    ops = [
        (e.start_ns, e.start_ns + e.duration_ns)
        for p in recorded.planes if p.name == "/device:TPU:0"
        for line in p.lines if line.name == "XLA Ops" for e in line.events]
    depth = busy = last = 0
    for t, d in sorted([(s0, 1) for s0, _ in ops] + [(e0, -1) for _, e0 in ops]):
        if depth > 0:
            busy += t - last
        depth, last = depth + d, t
    assert s["busy_s"] == pytest.approx(busy / 1e9, rel=1e-12)
    assert s["busy_s"] == pytest.approx(2.761591429, rel=1e-9)
    assert s["span_s"] == pytest.approx(2.791599735, rel=1e-9)
    # executables by their XLA module, the run id dropped
    assert s["modules"]["jit_round_fn"] == {"count": 1.0, "total_s": pytest.approx(1.81035012)}
    assert s["modules"]["jit_eval_all"]["count"] == 2.0
    assert s["modules"]["jit_eval_all"]["total_s"] == pytest.approx(0.950561452)
    # outermost operations first (a whole scan), then leaves; a scan's
    # body is not counted again at the top
    top = dict(s["top_ops"])
    assert top["top:while.144"] == pytest.approx(1.316199659)  # local training
    assert top["top:while.145"] == pytest.approx(0.485050595)  # the cohort's gather
    assert top["top:while"] == pytest.approx(0.887961765)      # evaluation's scan
    assert top["dynamic-update-slice.3"] == pytest.approx(0.411410721)
    assert not any(k.startswith("while") for k in top)  # control flow is no leaf
    assert s["kernels"]["fusion.1075"]["count"] == 15.0
    assert s["kernels"]["fusion.1075"]["total_s"] == pytest.approx(0.0703599)
    # idle: what busy leaves of the span, named by the host span around it
    gaps = dict(s["top_gaps"])
    assert sum(gaps.values()) == pytest.approx(s["span_s"] - s["busy_s"], abs=1e-5)


def _plane(name, **lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=n, start_ns=s, duration_ns=e - s) for n, s, e in evs])
        for ln, evs in lines.items()])


def test_chips_kernels_and_gaps():
    ms = 1_000_000
    chip0 = _plane(
        "/device:TPU:0",
        **{"XLA Modules": [("jit_step(1)", 0, 100 * ms)],
           "XLA Ops": [
               ("%fusion.1 = f32[8] fusion(...)", 0, 40 * ms),
               ("%all-reduce.3 = f32[8] all-reduce(...)", 30 * ms, 70 * ms),
               ("%my_kernel.2 = custom-call(...)", 80 * ms, 100 * ms)]})
    chip1 = _plane(
        "/device:TPU:1",
        **{"XLA Modules": [("jit_step(1)", 0, 60 * ms)],
           "XLA Ops": [("%fusion.1 = f32[8] fusion(...)", 0, 60 * ms)]})
    host = _plane("/host:CPU", main=[("round", 65 * ms, 90 * ms), ("other", 0, 5 * ms)])
    s = reduce_trace.reduce_profile(
        NS(planes=[chip1, host, chip0]), host_spans=["round"], kernel_names=["my_kernel"])
    assert s["devices"] == 2
    assert s["busy_s"] == pytest.approx((0.090 + 0.060) / 2)  # averaged over the chips
    assert s["span_s"] == pytest.approx(0.100)                 # the longest chip
    assert s["modules"]["jit_step"] == {"count": 1.0, "total_s": pytest.approx(0.080)}
    assert s["kernels"]["my_kernel"] == {"count": 0.5, "total_s": pytest.approx(0.010)}
    # chip 0 idles from 70 to 80 ms, inside the host's "round" span
    assert s["top_gaps"] == [["round", pytest.approx(0.010)]]


def test_no_device_plane_reads_nothing():
    s = reduce_trace.reduce_profile(NS(planes=[_plane("/host:CPU", main=[("round", 0, 5)])]))
    assert s["devices"] == 0 and s["busy_s"] == 0.0 and s["modules"] == {}


def test_union_and_names():
    assert reduce_trace.union_length([(0, 5), (3, 8), (10, 12)]) == 10
    assert reduce_trace.merge([(3, 8), (0, 5), (10, 12)]) == [(0, 8), (10, 12)]
    assert reduce_trace.short_name("%fusion.12 = f32[2] fusion(%a)") == "fusion.12"
    assert reduce_trace.op_kind("%all-reduce-start.3 = ...") == "all-reduce-start"
    assert reduce_trace.is_control_flow("%while.144 = (...) while(...)")
    assert reduce_trace.module_name("jit_round_fn(11606769906402365108)") == "jit_round_fn"
