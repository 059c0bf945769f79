"""The program's spans (``repro.tracing``) on the retrieval serving path:
under a profiler trace, one prefill, insert and decode step of a small
``RetrievalProgram`` emit exactly one span per launch and transfer, with
their args, and a query dispatches exactly two device programs (the
prefill and the insert); with no trace running a span records nothing,
and the ids and scores served are those of the plain tower and decode
steps.  The row a query's prefill makes lands in the slot it was
admitted to, and nowhere else."""
from __future__ import annotations

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.configs.retrieval import get_retrieval_config
from repro.launch import steps as steps_lib
from repro.serving.engine import PrefillPool
from repro.serving.retrieval import RetrievalProgram, init_retrieval_params
from repro.serving.scheduler import Request, ServeStats

SLOTS = 4
# the CPU client's event for each device program it runs
EXECUTE = "PjRtCpuExecutable::Execute"


@pytest.fixture(scope="module")
def served():
    """A warmed smoke-size program, its prefill pool and params."""
    rcfg = get_retrieval_config("smoke")
    params = init_retrieval_params(rcfg)
    program = RetrievalProgram(rcfg, n_slots=SLOTS)
    pool = PrefillPool(None, params, topk=rcfg.topk, program=program)
    _serve_one(program, pool, params, _request(rcfg, rid=0, slot=0))
    return rcfg, program, pool, params


def _request(rcfg, rid, slot):
    items = np.arange(5, 5 + rcfg.c_max - 2, dtype=np.int32)
    req = Request(rid=rid, prompt=items, max_gen=1, kind="oneshot")
    req.slot = slot
    return req


def _serve_one(program, pool, params, req):
    state = program.init_state(SLOTS)
    program.insert(state, req, pool.prefill_all([req])[0], ServeStats())
    return program.step(params, state)


def _host_events(log_dir, keep):
    from jax.profiler import ProfileData
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    return sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns,
                    {k: v for k, v in e.stats})
                   for plane in ProfileData.from_file(path).planes
                   if plane.name == "/host:CPU"
                   for line in plane.lines for e in line.events
                   if keep(e.name)),
                  key=lambda s: s[1])


def _program_spans(log_dir):
    return _host_events(log_dir, lambda n: n.startswith(tracing.PREFIX))


def test_spans_of_one_query_and_step(served, tmp_path):
    rcfg, program, pool, params = served
    req = _request(rcfg, rid=7, slot=1)
    jax.profiler.start_trace(str(tmp_path))
    try:
        ids, scores = _serve_one(program, pool, params, req)
    finally:
        jax.profiler.stop_trace()
    spans = _program_spans(tmp_path)
    args = lambda name: [s[3] for s in spans if s[0] == name]  # noqa: E731
    assert args("repro.prefill") == [{"rid": 7, "items": req.prompt_len}]
    assert args("repro.launch") == [
        {"fn": "prefill", "rid": 7}, {"fn": "insert", "rid": 7},
        {"fn": "decode", "live": 1}]
    # the items' upload carries the slot in its last column
    assert args("repro.h2d") == [
        {"what": "items", "bytes": 4 * (rcfg.c_max + 1)},
        {"what": "live", "bytes": SLOTS}]
    assert args("repro.wait") == [{"live": 1}]
    assert args("repro.d2h") == [{"what": "ids", "bytes": ids.nbytes},
                                 {"what": "scores", "bytes": scores.nbytes}]
    assert len(spans) == 1 + 3 + 2 + 1 + 2
    # the query's upload and its prefill launch nest in its prefill
    # span; the insert and the step come after it
    (_, lo, hi, _), = [s for s in spans if s[0] == "repro.prefill"]
    inside = [(s[0], s[3].get("fn", s[3].get("what"))) for s in spans
              if lo <= s[1] and s[2] <= hi and s[0] != "repro.prefill"]
    assert inside == [("repro.h2d", "items"), ("repro.launch", "prefill")]


def test_a_query_dispatches_two_device_programs(served, tmp_path):
    """From the start of the query's prefill to the end of its insert the
    host runs the jitted prefill and the jitted insert, and no eager
    program between them (no row index, no slot conversion)."""
    rcfg, program, pool, params = served
    req = _request(rcfg, rid=9, slot=SLOTS - 1)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve_one(program, pool, params, req)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path, lambda n: n == EXECUTE
                          or n.startswith(tracing.PREFIX))
    (_, lo, _, _), = [e for e in events if e[0] == "repro.prefill"]
    (_, _, hi, _), = [e for e in events if e[0] == "repro.launch"
                      and e[3].get("fn") == "insert"]
    # the decode step's program runs after the insert: the trace did see
    # the programs this client runs
    assert len([e for e in events if e[0] == EXECUTE and e[1] > hi]) == 1
    assert len([e for e in events if e[0] == EXECUTE
                and lo <= e[1] <= hi]) == 2


@pytest.mark.parametrize("slot", [0, 1, SLOTS - 1])
def test_the_row_lands_in_its_slot(served, slot):
    """The pool row of the admitted slot is the plain tower's row, bit
    for bit, and every other row keeps what it held."""
    rcfg, program, pool, params = served
    req = _request(rcfg, rid=11, slot=slot)
    state = program.init_state(SLOTS)
    before = np.arange(SLOTS * rcfg.m, dtype=np.float32).reshape(
        SLOTS, rcfg.m)
    state.pool = jnp.asarray(before)
    program.insert(state, req, pool.prefill_all([req])[0], ServeStats())
    got = np.asarray(state.pool)
    items = np.full((1, rcfg.c_max), -1, np.int32)
    items[0, :req.prompt_len] = req.prompt
    want = steps_lib.make_retrieval_prefill_step(rcfg)(
        params, jnp.asarray(items))[0]
    np.testing.assert_array_equal(got[slot], np.asarray(want))
    others = np.arange(SLOTS) != slot
    np.testing.assert_array_equal(got[others], before[others])
    assert state.live.tolist() == [s == slot for s in range(SLOTS)]


def test_no_trace_records_nothing_and_serves_the_same(served):
    rcfg, program, pool, params = served
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert tracing.span("launch", fn="x") is tracing.span("h2d")
    req = _request(rcfg, rid=3, slot=1)
    ids, scores = _serve_one(program, pool, params, req)
    # the same query through the plain tower and decode steps, each
    # jitted on its own, its row written to the slot from the host
    items = np.full((1, rcfg.c_max), -1, np.int32)
    items[0, :req.prompt_len] = req.prompt
    row = jax.jit(steps_lib.make_retrieval_prefill_step(rcfg))(
        params, jnp.asarray(items))[0]
    table = np.zeros((SLOTS, rcfg.m), np.float32)
    table[req.slot] = np.asarray(row)
    live = np.zeros(SLOTS, bool)
    live[req.slot] = True
    want_scores, want_ids = jax.jit(
        steps_lib.make_retrieval_decode_step(rcfg))(jnp.asarray(table),
                                                   jnp.asarray(live))
    np.testing.assert_array_equal(ids, np.asarray(want_ids))
    np.testing.assert_array_equal(scores, np.asarray(want_scores))
