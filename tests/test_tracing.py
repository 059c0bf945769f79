"""The program's spans (``repro.tracing``) on the retrieval serving path:
under a profiler trace, one prefill, insert and decode step of a small
``RetrievalProgram`` emit exactly one span per launch and transfer, with
their args; with no trace running a span records nothing, and the ids
and scores served are those of the program's jitted calls made
directly."""
from __future__ import annotations

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.configs.retrieval import get_retrieval_config
from repro.serving.engine import PrefillPool
from repro.serving.retrieval import RetrievalProgram, init_retrieval_params
from repro.serving.scheduler import Request, ServeStats

SLOTS = 2


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


def _program_spans(log_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    return sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns,
                    {k: v for k, v in e.stats})
                   for plane in ProfileData.from_file(path).planes
                   if plane.name == "/host:CPU"
                   for line in plane.lines for e in line.events
                   if e.name.startswith(tracing.PREFIX)),
                  key=lambda s: s[1])


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
        {"fn": "prefill", "rid": 7}, {"fn": "row", "rid": 7},
        {"fn": "insert", "rid": 7}, {"fn": "decode", "live": 1}]
    assert args("repro.h2d") == [
        {"what": "items", "bytes": 4 * rcfg.c_max},
        {"what": "slot", "bytes": 4}, {"what": "live", "bytes": SLOTS}]
    assert args("repro.wait") == [{"live": 1}]
    assert args("repro.d2h") == [{"what": "ids", "bytes": ids.nbytes},
                                 {"what": "scores", "bytes": scores.nbytes}]
    assert len(spans) == 1 + 4 + 3 + 1 + 2
    # the query's upload and its prefill and row launches nest in its
    # prefill span; the insert and the step come after it
    (_, lo, hi, _), = [s for s in spans if s[0] == "repro.prefill"]
    inside = [(s[0], s[3].get("fn", s[3].get("what"))) for s in spans
              if lo <= s[1] and s[2] <= hi and s[0] != "repro.prefill"]
    assert inside == [("repro.h2d", "items"), ("repro.launch", "prefill"),
                      ("repro.launch", "row")]


def test_no_trace_records_nothing_and_serves_the_same(served):
    rcfg, program, pool, params = served
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert tracing.span("launch", fn="x") is tracing.span("h2d")
    req = _request(rcfg, rid=3, slot=1)
    ids, scores = _serve_one(program, pool, params, req)
    # the same query through the program's jitted calls, unspanned
    items = np.full((1, rcfg.c_max), -1, np.int32)
    items[0, :req.prompt_len] = req.prompt
    row = program._prefill(params, jnp.asarray(items))[0]
    table = program._insert(jnp.zeros((SLOTS, rcfg.m), jnp.float32), row,
                            jnp.int32(req.slot))
    live = np.zeros(SLOTS, bool)
    live[req.slot] = True
    want_scores, want_ids = program._decode(table, jnp.asarray(live))
    np.testing.assert_array_equal(ids, np.asarray(want_ids))
    np.testing.assert_array_equal(scores, np.asarray(want_scores))
