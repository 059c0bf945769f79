"""The program's own spans (``repro.*``) and the five serving-loop
metrics read from them (``bench/layer_metrics/_program.py``): on a trace
recorded on a TPU v5e (``record_spans.py``: the movielens deployment at 8
slots, two iterations), on hand-made traces whose numbers are known, and
end to end through ``run.main`` at smoke sizes on the CPU."""
from __future__ import annotations

import json
import pathlib
import types

import pytest

from bench import harness, run, trace
from bench.layer_metrics import _program
from bench.tests.record_spans import ITERATIONS, SLOTS
from bench.tests.test_trace import _pd

DATA = pathlib.Path(__file__).parent / "data"
QUERIES = SLOTS * ITERATIONS
# the device programs each span of the serving path dispatches, in order:
# the eager row index is two programs, and the slot index's upload runs
# one (``jnp.int32`` converts on the device)
PROGRAMS = {("repro.launch", "prefill"): ["jit_step"],
            ("repro.launch", "row"): ["jit_dynamic_slice", "jit_squeeze"],
            ("repro.h2d", "slot"): ["jit_convert_element_type"],
            ("repro.launch", "insert"): ["jit__lambda"],
            ("repro.launch", "decode"): ["jit_step"]}
# how far the device's clock may read behind the host's (ns): 1.3-1.8 ms
# on the recording, 0.4-1.0 ms in traced runs of the cell
CLOCK_NS = 2e6

READERS = ("launches_per_query.retrieval", "transfers_per_query.retrieval",
           "launch_ms_per_query.retrieval",
           "transfer_ms_per_query.retrieval", "step_wait_ms.retrieval")
UNITS = ("launches", "transfers", "ms", "ms", "ms")


def _reader(name):
    return harness.load_module(harness.BENCH_DIR / "layer_metrics"
                               / f"{name}.py", f"bench_metric_{name}")


def _read_all(pd):
    """Every reader's value on ``pd``, as ``bench/run.py`` reads them."""
    spans, _ = _program.program_spans(pd)
    ctx = types.SimpleNamespace(trace=trace.reduce(pd), program_spans=spans)
    return {name: _reader(name).read(ctx) for name in READERS}


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(DATA / "v5e_retrieval_spans.xplane.pb"))
    spans, window = _program.program_spans(pd)
    return pd, spans, window, trace.reduce(pd)


def test_recorded_spans_are_counted_by_construction(recorded):
    _, spans, window, _ = recorded
    assert window is not None and len(spans) == 6 * QUERIES + 5 * ITERATIONS

    def named(name, **stats):
        return _program.program_spans_named(spans, window, name, **stats)

    assert [s.stats["rid"] for s in named("repro.prefill")] == \
        list(range(QUERIES))
    for fn in ("prefill", "row", "insert"):
        assert [s.stats["rid"] for s in named("repro.launch", fn=fn)] == \
            list(range(QUERIES))
    assert [s.stats["live"] for s in named("repro.launch", fn="decode")] \
        == [SLOTS] * ITERATIONS
    for what, n in (("items", QUERIES), ("slot", QUERIES),
                    ("live", ITERATIONS)):
        assert len(named("repro.h2d", what=what)) == n
    for what in ("ids", "scores"):
        assert len(named("repro.d2h", what=what)) == ITERATIONS
    assert len(named("repro.wait")) == ITERATIONS


def test_recorded_readers(recorded):
    pd, spans, _, _ = recorded
    got = _read_all(pd)
    assert got["launches_per_query.retrieval"] == pytest.approx(
        (3 * QUERIES + ITERATIONS) / QUERIES)
    assert got["transfers_per_query.retrieval"] == pytest.approx(
        (2 * QUERIES + 3 * ITERATIONS) / QUERIES)
    for name, kinds in (("launch_ms_per_query.retrieval", {"repro.launch"}),
                        ("transfer_ms_per_query.retrieval",
                         {"repro.h2d", "repro.d2h"})):
        ns = sum(s.dur for s in spans if s.name in kinds)
        assert got[name] == pytest.approx(ns / QUERIES / 1e6)
    wait_ns = sum(s.dur for s in spans if s.name == "repro.wait")
    assert got["step_wait_ms.retrieval"] == pytest.approx(
        wait_ns / ITERATIONS / 1e6)


def _sites(spans):
    """[(program name, its span)] in the host's order of dispatch."""
    return [(name, s) for s in spans for name in PROGRAMS.get(
        (s.name, s.stats.get("fn", s.stats.get("what"))), ())]


def test_every_device_program_has_its_span(recorded):
    """The device ran exactly the programs the spans dispatched, in their
    order: every launch on the path is wrapped.  Programs per query are
    the launches per query and two more, the row index's second program
    and the slot index's conversion."""
    pd, spans, _, tr = recorded
    modules = tr.chips[0].modules
    assert [m.name for m in modules] == [name for name, _ in _sites(spans)]
    assert len(modules) / QUERIES == pytest.approx(
        _read_all(pd)["launches_per_query.retrieval"] + 2)


def test_device_and_host_clocks_agree_to_a_constant_offset(recorded):
    """One offset of the device's clock behind the host's, under
    ``CLOCK_NS``, puts every program after the start of its span and every
    decode program before the end of the host's wait for it."""
    _, spans, _, tr = recorded
    pairs = list(zip(_sites(spans), tr.chips[0].modules))
    lower = max(s.start - m.start for (_, s), m in pairs)
    waits = [s for s in spans if s.name == "repro.wait"]
    decodes = [m for (_, s), m in pairs if s.stats.get("fn") == "decode"]
    upper = min(w.end - m.end for w, m in zip(waits, decodes))
    assert -CLOCK_NS <= lower <= upper <= CLOCK_NS
    for (_, launch), m in pairs:
        if launch.stats.get("fn") == "decode":
            wait = min((w for w in waits if w.start >= launch.end),
                       key=lambda w: w.start)
            assert launch.start - CLOCK_NS <= m.start
            assert m.end <= wait.end + CLOCK_NS


def test_recorded_harness_reduction_ignores_program_spans(recorded):
    """``bench/trace.py`` reads the recording as before: its spans and
    idle gaps are the harness's only."""
    _, _, window, tr = recorded
    assert tr.window == window
    assert {s.name for s in tr.spans} == {
        "bench.admit", "bench.prefill", "bench.insert", "bench.step",
        "bench.emit"}
    # a gap under no harness span (the window's edges) reads "none"
    assert {n for n, _ in tr.idle_gaps()} <= {s.name for s in tr.spans} | {
        "none"}
    assert len(tr.spans_named("bench.step")) == ITERATIONS
    assert 0.0 < tr.idle_share() < 1.0


def _query(rid, t):
    """One query's host spans from ``t`` (ns): a prefill of 100 holding
    the items' upload (10) and the prefill and row launches (20, 5), then
    the slot's upload (4) and the insert launch (6)."""
    return [("repro.prefill", t, 100, [("rid", rid), ("items", 18)]),
            ("repro.h2d", t + 10, 10, [("what", "items"), ("bytes", 72)]),
            ("repro.launch", t + 30, 20, [("fn", "prefill"), ("rid", rid)]),
            ("repro.launch", t + 60, 5, [("fn", "row"), ("rid", rid)]),
            ("repro.h2d", t + 110, 4, [("what", "slot"), ("bytes", 4)]),
            ("repro.launch", t + 120, 6, [("fn", "insert"), ("rid", rid)])]


def _step(t):
    """One decode step from ``t``: live mask up (2), launch (8), wait
    (50), ids and scores down (3 each)."""
    return [("repro.h2d", t, 2, [("what", "live"), ("bytes", 8)]),
            ("repro.launch", t + 10, 8, [("fn", "decode"), ("live", 2)]),
            ("repro.wait", t + 20, 50, [("live", 2)]),
            ("repro.d2h", t + 80, 3, [("what", "ids"), ("bytes", 80)]),
            ("repro.d2h", t + 90, 3, [("what", "scores"), ("bytes", 80)])]


def test_readers_count_and_time_per_query_and_step():
    # two iterations of two queries and a step; the first query's
    # prefill straddles the window's start, so the window holds three
    # queries, everything else of the first iteration and the second
    host = ([("bench.window", 50, 10_000), ("bench.step", 300, 100)]
            + _query(0, 0) + _query(1, 200) + _step(300)
            + _query(2, 500) + _query(3, 700) + _step(900))
    pd = _pd({"XLA Modules": [("jit_step(1)", 320, 40)]}, host)
    spans, window = _program.program_spans(pd)
    assert window == (50, 10_050)
    assert len(spans) == 4 * 6 + 2 * 5
    assert [s.stats["rid"] for s in _program.program_spans_named(
        spans, window, "repro.prefill")] == [1, 2, 3]
    assert len(_program.program_spans_named(
        spans, window, "repro.prefill", inside=False)) == 4
    assert len(_program.program_spans_named(
        spans, window, "repro.launch", fn="decode")) == 2
    got = _read_all(pd)
    # the straddling query's row and insert launches and its slot upload
    # lie inside: launches 2 + 3 * 3 + 2 steps, transfers 1 + 3 * 2 +
    # 2 steps' 3
    assert got["launches_per_query.retrieval"] == pytest.approx(13 / 3)
    assert got["transfers_per_query.retrieval"] == pytest.approx(13 / 3)
    launch_ns = (5 + 6) + 3 * (20 + 5 + 6) + 2 * 8
    assert got["launch_ms_per_query.retrieval"] == pytest.approx(
        launch_ns / 3 / 1e6)
    transfer_ns = 4 + 3 * (10 + 4) + 2 * (2 + 3 + 3)
    assert got["transfer_ms_per_query.retrieval"] == pytest.approx(
        transfer_ns / 3 / 1e6)
    assert got["step_wait_ms.retrieval"] == pytest.approx(50 / 1e6)


def test_readers_read_nothing_from_a_program_without_spans():
    """A program that emits no ``repro.*`` span (the parent of the spans)
    reports none of the five metrics, and raises nothing."""
    pd = _pd({"XLA Modules": [("jit_step(1)", 320, 40)]},
             [("bench.window", 0, 1000), ("bench.prefill", 10, 100,
                                          [("n", 2)]),
              ("bench.step", 300, 100, [("live", 2)])])
    assert _read_all(pd) == dict.fromkeys(READERS)


def test_program_spans_leave_the_harness_reduction_alone():
    """``bench/trace.py`` keeps the harness's spans only: a program span
    neither owns a device program nor an idle gap."""
    host = [("bench.window", 0, 1000), ("bench.step", 300, 100)] + _step(300)
    t = trace.reduce(_pd({"XLA Modules": [("jit_step(1)", 320, 40)]}, host))
    assert {s.name for s in t.spans} == {"bench.step"}
    assert {name for name, _ in t.idle_gaps()} <= {"bench.step", "none"}
    assert t.device_ns_under(t.spans_named("bench.step")) == 40


def test_traced_cell_reports_the_five_metrics(tiny_root, capsys):
    """``run.main`` with ``--trace 1`` at smoke sizes on the CPU: the
    readers find the run's trace and report every metric."""
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["per_layer"] += [{"name": n, "unit": u, "workloads": ["tiny.zipf"]}
                          for n, u in zip(READERS, UNITS)]
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    rc = run.main(["--workload", "tiny.zipf", "--seed", str(2**31 + 9),
                   "--seconds", "2", "--trace", "1"], root=tiny_root)
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True
    got = {n: res["metrics"][n]["value"] for n in READERS}
    # three launches and two uploads a query, and a step's launch, upload
    # and two copies shared by the queries it decoded
    assert 3 < got["launches_per_query.retrieval"] <= 4
    assert 2 < got["transfers_per_query.retrieval"] <= 5
    assert all(got[n] > 0 for n in READERS[2:])
