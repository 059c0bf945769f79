"""The trace reduction, on a small trace recorded on a TPU v5e (three
``bench.step`` spans, each running a bf16 matmul and the Bloom
decode-top-k kernel) and on hand-made traces whose numbers are known."""
from __future__ import annotations

import pathlib
import types

import pytest

from bench import trace

DATA = pathlib.Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    return trace.reduce(ProfileData.from_file(
        str(DATA / "v5e_small.xplane.pb")))


def test_recorded_trace_reads_device_and_spans(recorded):
    assert len(recorded.chips) == 1
    steps = recorded.spans_named("bench.step", inside=False)
    assert len(steps) == 3
    assert [int(s.stats["i"]) for s in steps] == [0, 1, 2]
    mods = recorded.chips[0].modules
    assert len(mods) == 6
    assert {m.name for m in mods} == {"jit__lambda"}
    busy = recorded.busy_s()
    assert busy == pytest.approx(sum(m.dur for m in mods) / 1e9)
    assert 0.0 < recorded.idle_share() < 1.0


def test_recorded_kernel_has_a_stable_name(recorded):
    calls = recorded.op_events("bloom_decode_topk_pallas")
    assert len(calls) == 3
    top = dict(recorded.top_ops(3))
    assert "bloom_decode_topk_pallas" in top
    assert top["bloom_decode_topk_pallas"] == pytest.approx(
        sum(e.dur for e in calls) / 1e9)


def test_recorded_programs_belong_to_their_spans(recorded):
    steps = recorded.spans_named("bench.step", inside=False)
    ns = recorded.device_ns_under(steps)
    # the window is the device programs' extent: the first matmul ran
    # before the first span opened on the host clock, and is still
    # attributed to the span that started right after it
    assert ns == pytest.approx(sum(m.dur for m in recorded.chips[0].modules))
    gaps = dict(recorded.idle_gaps())
    assert sum(gaps.values()) == pytest.approx(
        recorded.window_s() - recorded.busy_s())


@pytest.mark.parametrize("text,name", [
    ("%bloom_decode_topk_pallas.1 = (f32[1,8,10]) custom-call(...)",
     "bloom_decode_topk_pallas"),
    ("%copy-start = (bf16[512,512]) copy-start(%a.1)", "copy-start"),
    ("%fusion.12.3 = f32[] fusion(...)", "fusion"),
    ("while", "while"),
])
def test_op_names_drop_numeric_suffixes(text, name):
    assert trace.op_name(text) == name


def test_module_names_drop_fingerprints():
    assert trace.module_name("jit_step(15258237966670762812)") == "jit_step"
    assert trace.module_name("jit_step") == "jit_step"


def _pd(device_lines, host_events):
    ev = lambda name, s, d, stats=(): types.SimpleNamespace(  # noqa: E731
        name=name, start_ns=s, duration_ns=d, stats=list(stats))
    line = lambda name, evs: types.SimpleNamespace(  # noqa: E731
        name=name, events=[ev(*e) for e in evs])
    dev = types.SimpleNamespace(name="/device:TPU:0", lines=[
        line(n, evs) for n, evs in device_lines.items()])
    host = types.SimpleNamespace(name="/host:CPU", lines=[
        line("python", host_events)])
    return types.SimpleNamespace(planes=[dev, host])


def test_window_span_clips_busy_time_and_attributes_gaps():
    pd = _pd({"XLA Modules": [("jit_a(1)", 0, 100), ("jit_b(2)", 150, 50),
                              ("jit_a(1)", 300, 100)],
              "XLA Ops": [("%while.3 = x", 0, 100), ("%fusion.1 = y", 10, 30),
                          ("%fusion.2 = y", 50, 40), ("%k.4 = z", 150, 50),
                          ("%while.3 = x", 300, 100)]},
             [("bench.window", 50, 350), ("bench.step", 0, 120, [("live", 3)]),
              ("bench.idle", 200, 100), ("bench.step", 300, 100,
                                         [("live", 5)])])
    t = trace.reduce(pd)
    assert t.window == (50, 400)
    # busy in [50, 400): 50 of the first run, 50, and 100
    assert t.busy_s() == pytest.approx(200e-9)
    assert t.idle_share() == pytest.approx(150 / 350)
    # the gap [200, 300) lies under bench.idle, [100, 150) under bench.step
    assert dict(t.idle_gaps()) == pytest.approx(
        {"bench.idle": 100e-9, "bench.step": 50e-9})
    # self time: the while less its two fused bodies
    ops = dict(t.top_ops())
    # (the first while straddles the window's start: half of it counts)
    assert ops["while"] == pytest.approx(((100 - 30 - 40) / 2 + 100) * 1e-9)
    assert ops["fusion"] == pytest.approx(40e-9)
    # only the last step span lies inside the window
    steps = t.spans_named("bench.step")
    assert [int(s.stats["live"]) for s in steps] == [5]
    assert t.device_ns_under(steps) == 100
    assert t.busiest_module() == "jit_a"
    assert t.window_fraction("jit_a") == pytest.approx(1.5)
