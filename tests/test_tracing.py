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


# -- the token-LM program (``LMSlotProgram``) ---------------------------

LM_ARCH = "qwen1.5-0.5b"
LM_MAX_LEN = 24
LM_TOPK = 4
# the device programs each span of the LM path dispatches: the prompt's
# upload and the copies run none, a ``jnp.int32`` scalar converts on the
# device, and the eager ``[:, 0]`` is a slice and a squeeze
LM_PROGRAMS = {
    ("repro.h2d", "prompt"): 0, ("repro.launch", "expand"): 1,
    ("repro.launch", "prefill"): 1, ("repro.launch", "recover"): 1,
    ("repro.d2h", "first"): 0, ("repro.h2d", "slot"): 1,
    ("repro.launch", "insert"): 1, ("repro.h2d", "token"): 1,
    ("repro.h2d", "pos"): 1, ("repro.launch", "set_slot"): 1,
    ("repro.launch", "decode"): 1, ("repro.launch", "slice_next"): 1,
    ("repro.launch", "advance"): 1, ("repro.launch", "slice_top1"): 2,
    ("repro.wait", None): 0, ("repro.d2h", "ids"): 0,
    ("repro.launch", "drop"): 1}


@pytest.fixture(scope="module")
def lm_served():
    """A warmed smoke-size LM program, its prefill pool and params."""
    from repro import configs
    from repro.serving.engine import LMSlotProgram
    cfg = configs.get_smoke_config(LM_ARCH)
    params = steps_lib.cast_params_for_compute(
        steps_lib.init_fn_for(cfg)(jax.random.PRNGKey(0)), cfg)
    program = LMSlotProgram(cfg, topk=LM_TOPK, n_slots=SLOTS,
                            max_len=LM_MAX_LEN)
    pool = PrefillPool(cfg, params, topk=LM_TOPK, program=program)
    _serve_lm(program, pool, params, _lm_request(cfg, rid=0, slot=0))
    return cfg, program, pool, params


def _lm_request(cfg, rid, slot, max_gen=2, prompt_len=8):
    prompt = (np.arange(prompt_len, dtype=np.int32) * 37 + rid) % cfg.vocab
    req = Request(rid=rid, prompt=prompt, max_gen=max_gen)
    req.slot = slot
    return req


def _serve_lm(program, pool, params, req):
    """One request through prefill, insert and decode steps until it
    retires; returns its tokens."""
    state = program.init_state(SLOTS)
    stats = ServeStats()
    live = program.insert(state, req, pool.prefill_all([req])[0], stats)
    while live:
        out = program.step(params, state)
        live = not program.emit(state, req, req.slot, out, stats)
    return list(req.tokens)


def _lm_traced(lm_served, tmp_path, req):
    cfg, program, pool, params = lm_served
    jax.profiler.start_trace(str(tmp_path))
    try:
        tokens = _serve_lm(program, pool, params, req)
    finally:
        jax.profiler.stop_trace()
    return tokens, _host_events(tmp_path, lambda n: n == EXECUTE
                                or n.startswith(tracing.PREFIX))


def _kind(s):
    return s[3].get("fn", s[3].get("what"))


def test_lm_spans_of_one_request_and_step(lm_served, tmp_path):
    """A request of two tokens: its prefill and insert, one decode step
    and the retirement that step's token causes, each launch and transfer
    spanned in the order the host makes them."""
    cfg = lm_served[0]
    req = _lm_request(cfg, rid=7, slot=2)
    tokens, events = _lm_traced(lm_served, tmp_path, req)
    assert len(tokens) == 2
    spans = [s for s in events if s[0] != EXECUTE]
    ordered = [(s[0], _kind(s), s[3].get("rid", s[3].get("live")))
               for s in spans if s[0] in ("repro.launch", "repro.h2d",
                                          "repro.d2h", "repro.wait")]
    per_request = [("repro.h2d", "prompt", None),
                   ("repro.launch", "expand", 7),
                   ("repro.launch", "prefill", 7),
                   ("repro.launch", "recover", 7),
                   ("repro.d2h", "first", None),
                   ("repro.h2d", "slot", None),
                   ("repro.launch", "insert", 7),
                   ("repro.h2d", "slot", None), ("repro.h2d", "token", None),
                   ("repro.h2d", "pos", None),
                   ("repro.launch", "set_slot", 7)]
    step = [("repro.launch", "decode", 1), ("repro.launch", "slice_next", 1),
            ("repro.launch", "advance", 1), ("repro.launch", "slice_top1", 1),
            ("repro.wait", None, 1), ("repro.d2h", "ids", None)]
    retire = [("repro.h2d", "slot", None), ("repro.launch", "drop", 7)]
    assert ordered == per_request + step + retire
    # the prompt's upload, its launches and the first token's copy nest
    # in the request's prefill span; the insert follows it
    (_, lo, hi, args), = [s for s in spans if s[0] == "repro.prefill"]
    assert args == {"rid": 7, "items": req.prompt_len}
    assert [(s[0], _kind(s)) for s in spans
            if lo <= s[1] and s[2] <= hi and s[0] != "repro.prefill"] == \
        [(n, k) for n, k, _ in per_request[:5]]


def test_lm_transfers_give_their_bytes(lm_served, tmp_path):
    cfg = lm_served[0]
    req = _lm_request(cfg, rid=8, slot=1, prompt_len=11)
    _, events = _lm_traced(lm_served, tmp_path, req)
    got = [(s[0], s[3]["what"], s[3]["bytes"]) for s in events
           if s[0] in ("repro.h2d", "repro.d2h")]
    assert got == [("repro.h2d", "prompt", 4 * 11),
                   ("repro.d2h", "first", 4 * LM_TOPK),
                   ("repro.h2d", "slot", 4), ("repro.h2d", "slot", 4),
                   ("repro.h2d", "token", 4), ("repro.h2d", "pos", 4),
                   ("repro.d2h", "ids", 4 * SLOTS),
                   ("repro.h2d", "slot", 4)]


def test_lm_every_device_program_runs_under_its_span(lm_served, tmp_path):
    """Every device program a request and its steps run, from the start
    of its prefill, is dispatched inside a launch or transfer span, as
    many under each as ``LM_PROGRAMS`` says: no eager call on the path
    is left unnamed.  (The pool's allocation before it is set-up.)"""
    cfg = lm_served[0]
    req = _lm_request(cfg, rid=9, slot=SLOTS - 1, max_gen=3)
    _, events = _lm_traced(lm_served, tmp_path, req)
    spans = [s for s in events if s[0] in ("repro.launch", "repro.h2d",
                                           "repro.d2h", "repro.wait")]
    (_, start, _, _), = [e for e in events if e[0] == "repro.prefill"]
    runs = [e for e in events if e[0] == EXECUTE and e[1] >= start]
    assert runs
    under = [[e for e in runs if s[1] <= e[1] and e[2] <= s[2]]
             for s in spans]
    assert sum(len(u) for u in under) == len(runs)
    for s, u in zip(spans, under):
        assert len(u) == LM_PROGRAMS[(s[0], _kind(s))], s


def test_lm_no_trace_records_nothing_and_serves_the_same(lm_served,
                                                         tmp_path,
                                                         monkeypatch):
    """Outside a profiler session the path makes no annotation; the
    tokens served are the same with the trace on and off, and the same
    as those of the plain prefill, recovery and pool decode steps, each
    jitted on its own, with the slot state kept on the host."""
    from repro.models import io as io_lib
    from repro.models import transformer as tf
    cfg, program, pool, params = lm_served
    made = []

    class Counted(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            made.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counted)
    assert not jax.profiler.TraceAnnotation.is_enabled()
    max_gen, slot = 6, 1
    off = _serve_lm(program, pool, params,
                    _lm_request(cfg, rid=3, slot=slot, max_gen=max_gen))
    assert made == []
    on, _ = _lm_traced(lm_served, tmp_path,
                       _lm_request(cfg, rid=3, slot=slot, max_gen=max_gen))
    assert "repro.launch" in made
    assert on == off and len(off) == max_gen

    req = _lm_request(cfg, rid=3, slot=slot, max_gen=max_gen)
    pre = jax.jit(steps_lib.make_prefill_step(cfg))(
        params, {"tokens": jnp.asarray(req.prompt)[None, :]})
    _, ids = jax.jit(lambda lg: io_lib.recover_topk(cfg, lg, topk=LM_TOPK))(
        pre["last_logits"])
    want = [int(np.asarray(ids)[0, 0])]
    caches = jax.jit(steps_lib.insert_cache_slot)(
        tf.init_lm_cache(cfg, SLOTS, LM_MAX_LEN, dtype=jnp.dtype(cfg.dtype)),
        pre["caches"], slot)
    decode = jax.jit(steps_lib.make_slot_decode_step(cfg, topk=LM_TOPK))
    active = np.arange(SLOTS) == slot
    while len(want) < max_gen:
        tokens = np.zeros((SLOTS, 1), np.int32)
        tokens[slot, 0] = want[-1]
        pos = np.zeros((SLOTS,), np.int32)
        pos[slot] = req.prompt_len + len(want) - 1
        out = decode(params, jnp.asarray(tokens), caches, jnp.asarray(pos),
                     jnp.asarray(active))
        caches = out["caches"]
        want.append(int(np.asarray(out["topk_ids"])[slot, 0]))
    assert off == want


def test_lm_decode_span_names_its_kv_write(lm_served, tmp_path):
    """The decode span says how the compiled pool step writes its KV
    rows: in place on one device, by the masked select under a ``dist``
    (a one-device mesh here).  Either way a step makes the same
    launches, and the two serve the same tokens."""
    from repro.launch.mesh import auto_mesh
    from repro.launch.sharding import DistContext
    from repro.serving.engine import LMSlotProgram
    cfg, program, pool, params = lm_served
    dist = DistContext(auto_mesh((1, 1), ("data", "model"),
                                 devices=jax.devices()[:1]))
    masked = LMSlotProgram(cfg, topk=LM_TOPK, n_slots=SLOTS,
                           max_len=LM_MAX_LEN, dist=dist)
    served = {}
    for prog in (program, masked):
        req = _lm_request(cfg, rid=11, slot=2, max_gen=3)
        tokens, events = _lm_traced(
            (cfg, prog, PrefillPool(cfg, params, topk=LM_TOPK,
                                    program=prog), params),
            tmp_path / prog.kv_write, req)
        launches = [s[3] for s in events if s[0] == "repro.launch"
                    and "live" in s[3]]
        served[prog.kv_write] = tokens, launches
    assert set(served) == {"inplace", "masked"}
    for kv_write, (tokens, launches) in served.items():
        assert [a["fn"] for a in launches] == [
            "decode", "slice_next", "advance", "slice_top1"] * 2
        assert [a.get("kv_write") for a in launches if a["fn"] == "decode"] \
            == [kv_write] * 2
    assert served["inplace"][0] == served["masked"][0]
