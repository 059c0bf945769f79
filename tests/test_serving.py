"""Deterministic simulation tests for the continuous-batching engine.

Everything here runs seeded on CPU (interpret-mode friendly shapes):
  * token-level equivalence — a request served through the slot pool is
    BIT-identical to serving it alone through the static path (per-row
    decode math is row-independent; the masked slot cache write stores
    the same values as the static dynamic-slice write);
  * scheduler soundness on the real engine — no slot double-assigned,
    every admitted request completes;
  * the throughput claim — continuous batching finishes the mixed-length
    loadgen workload in >= 1.5x fewer decode steps than static batching.
"""
import jax
import numpy as np
import pytest

from repro import configs
from repro.launch import steps as steps_lib
from repro.serving import (Engine, LoadSpec, Request, make_workload,
                           mixed_length_workload)

ARCH = "qwen1.5-0.5b"
N_SLOTS = 3
MAX_LEN = 40


@pytest.fixture(scope="module")
def served():
    """One continuous run of the canonical mixed-length workload, plus
    the per-request solo static runs, shared across the tests below."""
    cfg = configs.get_smoke_config(ARCH)
    params = steps_lib.cast_params_for_compute(
        steps_lib.init_fn_for(cfg)(jax.random.PRNGKey(0)), cfg)

    engine = Engine(cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN, topk=4)
    results, stats = engine.run(mixed_length_workload(cfg.vocab, 10, seed=0))

    solo = Engine(cfg, params, n_slots=1, max_len=MAX_LEN, topk=4)
    solo_tokens = {}
    for req in mixed_length_workload(cfg.vocab, 10, seed=0):
        req.arrival_step = 0
        r, _ = solo.run_static([req])
        solo_tokens[req.rid] = r[req.rid].tokens

    static_results, static_stats = engine.run_static(
        mixed_length_workload(cfg.vocab, 10, seed=0))
    return dict(cfg=cfg, engine=engine, results=results, stats=stats,
                solo_tokens=solo_tokens, static_results=static_results,
                static_stats=static_stats)


def test_tokens_bit_identical_to_solo_static(served):
    """Paper Fig. 3 serving path: pooling requests must not change a
    single recovered token vs serving each request alone."""
    assert served["results"], "workload produced no results"
    for rid, req in served["results"].items():
        assert req.tokens == served["solo_tokens"][rid], (
            f"req {rid}: continuous {req.tokens} != solo "
            f"{served['solo_tokens'][rid]}")


def test_every_request_completes_no_slot_double_assigned(served):
    results = served["results"]
    assert all(r.done for r in results.values())
    assert all(len(r.tokens) >= 1 for r in results.values())
    # each request respects its generation budget
    assert all(len(r.tokens) <= r.max_gen for r in results.values())

    # reconstruct slot occupancy from the scheduler event log (ordered by
    # the global event sequence — several events can share a clock step)
    from conftest import assert_slot_log_sound
    sched = served["engine"]._sched
    assert {rid for _, _, rid, _ in sched.admissions} == set(results)
    assert len(sched.admissions) == len(results)     # admitted exactly once
    assert len(sched.releases) == len(results)
    assert_slot_log_sound(sched, N_SLOTS)


def test_continuous_beats_static_by_1_5x(served):
    cont, stat = served["stats"], served["static_stats"]
    assert cont.decode_steps > 0
    assert stat.decode_steps >= 1.5 * cont.decode_steps, (
        f"static {stat.decode_steps} vs continuous {cont.decode_steps}")
    assert cont.utilization > stat.utilization
    # same total work either way — only the schedule differs
    assert cont.tokens_out == stat.tokens_out
    for rid, req in served["static_results"].items():
        assert req.tokens == served["solo_tokens"][rid]


def test_eos_stops_a_slot_early(served):
    """Rerun the same deterministic workload with eos_id set to a token
    known (from the baseline run) to appear mid-stream; that request must
    retire at the eos while the others are unaffected up to their own
    first eos occurrence."""
    baseline = served["solo_tokens"]
    victim = max(baseline, key=lambda r: len(baseline[r]))
    toks = baseline[victim]
    assert len(toks) >= 3, "need a long request to cut short"
    eos = toks[len(toks) // 2]

    cfg = served["cfg"]
    engine = Engine(cfg, served["engine"].params, n_slots=N_SLOTS,
                    max_len=MAX_LEN, topk=4, eos_id=eos)
    results, _ = engine.run(mixed_length_workload(cfg.vocab, 10, seed=0))
    for rid, req in results.items():
        full = baseline[rid]
        cut = (full[:full.index(eos) + 1] if eos in full else full)
        assert req.tokens == cut, (rid, req.tokens, cut)
    assert len(results[victim].tokens) < len(baseline[victim])


def test_engine_rejects_overlong_request():
    cfg = configs.get_smoke_config(ARCH)
    params = steps_lib.cast_params_for_compute(
        steps_lib.init_fn_for(cfg)(jax.random.PRNGKey(0)), cfg)
    engine = Engine(cfg, params, n_slots=1, max_len=8, topk=2)
    req = Request(rid=0, prompt=np.zeros((6,), np.int32), max_gen=6)
    with pytest.raises(AssertionError, match="exceeds pool max_len"):
        engine.run([req])


def test_odd_max_len_pool_holds_whole_lane_windows(served):
    """``MAX_LEN`` (40) is no multiple of 128: the pool is allocated at
    128 positions, the length its in-place row write moves, and still
    admits only what fits ``MAX_LEN``.  A request that fills ``MAX_LEN``
    to its last position, beside a shorter one, serves the tokens it
    serves alone."""
    cfg, params = served["cfg"], served["engine"].params
    engine = Engine(cfg, params, n_slots=2, max_len=MAX_LEN, topk=4)
    prog = engine.program
    assert prog.kv_write == "inplace"
    assert {a.shape[-1] for a in jax.tree.leaves(prog._pool_template)} \
        == {128}
    rng = np.random.default_rng(3)

    def reqs():
        return [Request(rid=i, prompt=rng.integers(0, cfg.vocab, n)
                        .astype(np.int32), max_gen=g)
                for i, (n, g) in enumerate(((MAX_LEN - 6, 6), (5, 4)))]

    results, _ = engine.run(reqs())
    solo = Engine(cfg, params, n_slots=1, max_len=MAX_LEN, topk=4)
    rng = np.random.default_rng(3)
    for req in reqs():
        alone, _ = solo.run_static([req])
        assert results[req.rid].tokens == alone[req.rid].tokens
    assert len(results[0].tokens) == 6


def test_prefill_pool_is_schedule_and_token_invariant(served):
    """Prefill pool satellite (DESIGN.md §9): a burst served through a
    3-worker pool produces the EXACT tokens of the 1-worker pool (and of
    solo static serving), with FIFO dispatch spreading the burst across
    all workers and the summed virtual queue wait strictly shrinking."""
    from repro.serving import LoadSpec, burst_workload

    cfg = served["cfg"]
    spec = LoadSpec(n_requests=6, vocab=cfg.vocab, prompt_lens=(6, 10, 14),
                    gen_lens=(3, 6), seed=1)
    max_len = 24

    stats = {}
    tokens = {}
    for n_workers in (1, 3):
        engine = Engine(cfg, served["engine"].params, n_slots=6,
                        max_len=max_len, topk=4,
                        prefill_workers=n_workers)
        results, st = engine.run(burst_workload(spec))
        tokens[n_workers] = {rid: r.tokens for rid, r in results.items()}
        stats[n_workers] = (engine.prefill_pool.stats, st)
    assert tokens[1] == tokens[3]
    assert stats[1][1].decode_steps == stats[3][1].decode_steps

    pool1, pool3 = stats[1][0], stats[3][0]
    assert pool1["jobs"] == pool3["jobs"] == 6
    assert pool1["per_worker"] == [6]
    assert len(pool3["per_worker"]) == 3
    assert sum(pool3["per_worker"]) == 6
    assert all(c > 0 for c in pool3["per_worker"])   # burst spreads out
    assert pool3["max_queue_depth"] == pool1["max_queue_depth"] == 6
    # head-of-line blocking: 1 worker serializes the burst, 3 overlap it
    assert pool3["wait_units"] < pool1["wait_units"]


def test_engine_prefill_retry_and_reject_via_failpoints(served):
    """Failure-model satellite on the REAL engine: a prefill fault below
    the attempt cap is retried on another worker and every token stays
    bit-identical; AT the cap the victim is REJECTed (slot freed, logged)
    while every other request is served untouched."""
    from repro.serving import FailPlan, PREFILL_MAX_ATTEMPTS

    cfg = served["cfg"]
    baseline = served["solo_tokens"]
    victim = max(baseline, key=lambda r: len(baseline[r]))

    # below the cap: retries absorb the fault — schedule/token invariant
    engine = Engine(cfg, served["engine"].params, n_slots=N_SLOTS,
                    max_len=MAX_LEN, topk=4, prefill_workers=2,
                    failpoints=FailPlan.parse(
                        f"fail_prefill:{victim}:{PREFILL_MAX_ATTEMPTS - 1}"))
    results, st = engine.run(mixed_length_workload(cfg.vocab, 10, seed=0))
    assert st.rejects == 0
    assert engine.prefill_pool.stats["retries"] == PREFILL_MAX_ATTEMPTS - 1
    assert engine.prefill_pool.stats["rejects"] == 0
    for rid, req in results.items():
        assert req.tokens == baseline[rid]

    # at the cap: REJECT — the victim ends unserved, everyone else is
    # bit-identical to the fault-free baseline
    engine = Engine(cfg, served["engine"].params, n_slots=N_SLOTS,
                    max_len=MAX_LEN, topk=4, prefill_workers=2,
                    failpoints=FailPlan.parse(
                        f"fail_prefill:{victim}:{PREFILL_MAX_ATTEMPTS}"))
    results, st = engine.run(mixed_length_workload(cfg.vocab, 10, seed=0))
    assert st.rejects == 1
    assert engine.prefill_pool.stats["rejects"] == 1
    assert results[victim].rejected and results[victim].tokens == []
    for rid, req in results.items():
        if rid != victim:
            assert not req.rejected
            assert req.tokens == baseline[rid]
    from conftest import assert_slot_log_sound
    assert_slot_log_sound(engine._sched, N_SLOTS)


def test_engine_prefill_crash_propagates(served, monkeypatch):
    """Only the injected PrefillFault is retried: any other prefill error
    (a kernel that fails to compile, a device fault) escapes Engine.run
    instead of becoming retries and a REJECT with empty tokens."""
    cfg = served["cfg"]
    engine = Engine(cfg, served["engine"].params, n_slots=N_SLOTS,
                    max_len=MAX_LEN, topk=4, prefill_workers=2)

    def crash(req):
        raise RuntimeError("prefill crashed")

    for worker in engine.prefill_pool.workers:
        monkeypatch.setattr(worker, "prefill", crash)
    with pytest.raises(RuntimeError, match="prefill crashed"):
        engine.run(mixed_length_workload(cfg.vocab, 10, seed=0))
    assert engine.prefill_pool.stats["retries"] == 0
    assert engine.prefill_pool.stats["rejects"] == 0


def test_overload_sheds_and_degrades_without_recompiling(served):
    """ISSUE 10 on the single-host engine: a surge + slow_decode plan
    overloads the pool under an AdmissionPolicy; expired/over-bound
    requests are SHED (never admitted, zero tokens), every SERVED
    request's tokens stay bit-identical to the unloaded solo baseline
    (degradation narrows the served top-k; the next token is the top-1
    id, invariant under the width), the ladder escalates AND restores,
    and no DEGRADE/RESTORE ever compiles a new decode executable."""
    from repro.serving import AdmissionPolicy, FailPlan
    from repro.serving.admission import STAGE_NORMAL

    cfg = served["cfg"]
    baseline = served["solo_tokens"]
    policy = AdmissionPolicy(max_queue_depth=2, pressure_window=2,
                             degrade_lo=0.25, degrade_hi=0.5,
                             restore_below=0.1)
    engine = Engine(cfg, served["engine"].params, n_slots=N_SLOTS,
                    max_len=MAX_LEN, topk=4,
                    failpoints=FailPlan.parse("surge:3@1,slow_decode:3@2"),
                    admission_policy=policy)
    workload = mixed_length_workload(cfg.vocab, 10, seed=0)
    for r in workload:
        r.deadline_step = r.arrival_step + 6
    results, st = engine.run(workload)

    shed = {rid for rid, r in results.items() if r.shed}
    assert st.sheds == len(shed) > 0, "surge shed nothing — vacuous"
    assert st.degrades >= 2, "ladder never escalated AND restored"
    degr = engine._sched.degrades
    assert any(new > old for _, old, new, _ in degr)
    assert any(new < old for _, old, new, _ in degr)
    assert len(engine._sched.sheds) == st.sheds
    for rid, r in results.items():
        assert r.done, rid
        if r.shed:
            assert r.admitted_step < 0 and r.tokens == [], rid
        else:
            assert r.tokens == baseline[rid], (
                f"req {rid} token drift under degradation")
    # zero recompiles: each pre-built stage executable compiled at most
    # once; stage 0 exactly once; and the program ends restored
    for stage, fn in engine.program._stage_decodes.items():
        assert fn._cache_size() <= 1, f"stage {stage} recompiled"
    assert engine.program._stage_decodes[STAGE_NORMAL]._cache_size() == 1
    assert engine.program._stage == STAGE_NORMAL
    from conftest import assert_slot_log_sound
    assert_slot_log_sound(engine._sched, N_SLOTS)

    # the identical (workload, plan, policy) replays the identical shed
    # set and log — shed decisions are deterministic
    twin_engine = Engine(cfg, served["engine"].params, n_slots=N_SLOTS,
                         max_len=MAX_LEN, topk=4,
                         failpoints=FailPlan.parse(
                             "surge:3@1,slow_decode:3@2"),
                         admission_policy=policy)
    twin_wl = mixed_length_workload(cfg.vocab, 10, seed=0)
    for r in twin_wl:
        r.deadline_step = r.arrival_step + 6
    twin_results, twin_st = twin_engine.run(twin_wl)
    assert {rid for rid, r in twin_results.items() if r.shed} == shed
    assert twin_engine._sched.sheds == engine._sched.sheds
    assert twin_engine._sched.degrades == engine._sched.degrades
    assert (twin_st.as_row(), twin_st.sheds, twin_st.degrades) == \
        (st.as_row(), st.sheds, st.degrades)   # wall_s alone may differ


def test_engine_under_a_one_device_mesh_serves_the_same_tokens(served):
    """Under a ``dist`` the program places its pool by the slot-pool
    specs and pins every step's layout; on a one-device mesh the slots
    stay unsharded, the tokens are those served alone, and the decode
    step compiles once."""
    from repro.launch.mesh import make_local_mesh
    from repro.launch.sharding import DistContext
    cfg = served["cfg"]
    engine = Engine(cfg, served["engine"].params, n_slots=N_SLOTS,
                    max_len=MAX_LEN, topk=4,
                    dist=DistContext(make_local_mesh()))
    results, _ = engine.run(mixed_length_workload(cfg.vocab, 10, seed=0))
    for rid, req in results.items():
        assert req.tokens == served["solo_tokens"][rid], rid
    assert engine.program._decode._cache_size() == 1


def _two_live_states(served):
    """Two pool states of the served program holding the same three
    requests, one step into decoding."""
    engine, cfg = served["engine"], served["cfg"]
    prog = engine.program
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, n)
                    .astype(np.int32), max_gen=8)
            for i, n in enumerate((5, 9, 7))]
    for slot, req in enumerate(reqs):
        req.slot = slot
    payloads = engine.prefill_pool.prefill_all(reqs)
    states = []
    for _ in range(2):
        state = prog.init_state(N_SLOTS)
        for req, payload in zip(reqs, payloads):
            prog.insert_caches(state, req, payload[0])
            prog.start(state, req, payload[1])
        prog.step(engine.params, state)
        states.append(state)
    return prog, engine.params, states


def _host(state):
    return (jax.tree.map(np.asarray, state.caches),
            np.asarray(state.tokens), np.asarray(state.pos),
            np.asarray(state.active), state.live.copy())


@pytest.mark.parametrize("op", ["compact", "clear"])
def test_program_remaps_and_clears_its_device_slot_state(served, op):
    """The control plane's two slot-range operations on the device-
    resident state.  ``compact`` permutes the caches and the slot
    vectors, ``perm[new] = old``, as the host-side remap did, and the
    next step's top-1 ids follow the permutation.  ``clear`` stops
    exactly the cleared rows decoding (ids 0, position held) and zeros
    their tokens and positions; the other rows decode as before."""
    prog, params, (ref, got) = _two_live_states(served)
    prog.drop(ref, 2)
    prog.drop(got, 2)
    caches, tokens, pos, active, live = _host(got)
    if op == "compact":
        perm = np.array([2, 0, 1], np.int32)
        prog.compact(got, perm)
        want_caches = jax.tree.map(lambda b: b[:, perm], caches)
        want = (tokens[perm], pos[perm], active[perm], live[perm])
    else:
        perm = np.arange(N_SLOTS)
        prog.clear(got, 1, 2)
        want_caches = caches
        keep = perm != 1
        want = (tokens * keep[:, None], pos * keep, active & keep,
                live & keep)
    now = _host(got)
    for a, b in zip(jax.tree.leaves(now[0]), jax.tree.leaves(want_caches)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(now[1:], want):
        np.testing.assert_array_equal(a, b)
    ids_ref = prog.step(params, ref)
    ids = prog.step(params, got)
    decoding = want[2]
    np.testing.assert_array_equal(ids[decoding], ids_ref[perm][decoding])
    np.testing.assert_array_equal(ids[~decoding], 0)
    np.testing.assert_array_equal(np.asarray(got.pos),
                                  want[1] + decoding)


def test_loadgen_is_deterministic():
    spec = LoadSpec(n_requests=20, vocab=128, rate=0.7, seed=123)
    a, b = make_workload(spec), make_workload(spec)
    assert [r.arrival_step for r in a] == [r.arrival_step for r in b]
    assert [r.max_gen for r in a] == [r.max_gen for r in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    # arrivals are sorted and lengths come from the configured mix
    arr = [r.arrival_step for r in a]
    assert arr == sorted(arr)
    assert {r.prompt_len for r in a} <= set(spec.prompt_lens)
    assert {r.max_gen for r in a} <= set(spec.gen_lens)
