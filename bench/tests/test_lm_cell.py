"""The qwen1.5-0.5b serving cell (``qwen1.5-0.5b.chat_overload``): its
configuration at the published widths (shapes only), its traffic mix,
the four readers it adds (on a traced ``tiny.chat`` run on the CPU and
on hand-made traces whose numbers are known), the byte count of the
decode step's roofline, and the LM program served through its decode
cache against the plain float32 reference."""
from __future__ import annotations

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, peaks, run, trace, work, work_lm
from bench.layer_metrics import _program
from bench.reference import lm as ref_lm
from bench.systems import lm
from bench.tests.conftest import LM, TRAFFIC
from bench.tests.test_trace import _pd

CELL = "qwen1.5-0.5b.chat_overload"
SPAN_READERS = ("launches_per_token.lm", "transfer_ms_per_token.lm",
                "step_wait_ms.lm")
READERS = SPAN_READERS + ("decode_step_roofline.lm",)
UNITS = ("launches", "ms", "ms", "%")
SEED = 2**33 + 17


def _spec():
    return harness.benchmark_spec()


def _cell_files():
    spec = _spec()
    cell = harness.find(spec["workloads"], CELL, "workload")
    entry = harness.find(spec["configs"], cell["config"], "config")
    return (cell, entry, harness.load_json(harness.ROOT / entry["file"]),
            harness.load_json(harness.BENCH_DIR / "traffic"
                              / f"{cell['traffic']}.json"))


def _reader(name):
    return harness.load_module(harness.BENCH_DIR / "layer_metrics"
                               / f"{name}.py", f"bench_metric_{name}")


def test_served_config_is_the_published_model_at_its_widths():
    """The cell's file holds every key of the recorded configuration, cuts
    nothing, states a limit, and the program built from it has the
    published shapes (``jax.eval_shape``: no weights are made)."""
    _, entry, cfg, _ = _cell_files()
    recorded = harness.load_json(harness.BENCH_DIR / "configs"
                                 / "qwen1.5-0.5b.json")
    assert {k: v for k, v in cfg.items() if k in recorded
            and k != "limits"} == {k: v for k, v in recorded.items()
                                   if k != "limits"}
    assert cfg["reduced"] == entry["reduced"] == []
    assert entry["source"] == cfg["source"]
    assert cfg["limits"]["token_gap"] > 0
    mc = lm.program_config(cfg)
    leaves, _ = lm._leaf_specs(mc)
    assert leaves["io/embed"][0] == (30208, 1024)
    matmul = sum(int(np.prod(shape)) for shape, _, kind in leaves.values()
                 if kind in ("matrix", "embed"))
    assert matmul == work.lm_matmul_params(cfg)
    assert {shape[0] for shape, _, kind in leaves.values()
            if kind == "matrix"} == {24}


def test_chat_overload_is_the_chat_mix_with_a_rate():
    cell, _, _, mix = _cell_files()
    chat = harness.load_json(harness.BENCH_DIR / "traffic" / "chat.json")
    assert cell["chips"] == 1
    assert {k: v for k, v in mix.items() if k != "rate_qps"} == chat
    assert mix["rate_qps"] > 0
    assert max(mix["prompt_lens"]) + max(mix["output_lens"]) \
        <= mix["max_len"]


def test_every_metric_has_its_reader_and_the_cell_its_metrics():
    spec = _spec()
    for m in spec["per_layer"]:
        assert (harness.BENCH_DIR / "layer_metrics"
                / f"{m['name']}.py").is_file(), m["name"]
    ours = {m["name"] for m in harness.cell_metrics(spec, CELL,
                                                    "per_layer")}
    assert ours == {"decode_step_ms.lm", "prefill_ms.lm", "idle_share.lm",
                    "mfu.lm", *READERS}
    assert {m["name"] for m in harness.cell_metrics(
        spec, CELL, "end_to_end")} == {"queries_per_s", "setup_s"}


def test_decode_step_bytes_by_hand():
    """At the tiny LM (float32, 2 layers of 64 wide, 4 heads of 16, MLP
    128, m = 128, k = 3, vocabulary 512) for 3 live rows attending 40
    positions with a top-4: weights 2 * (4 * 64 * 64 + 3 * 64 * 128) +
    64 * 128 = 90,112 at 4 bytes; keys and values 40 * 2 layers * 2 * 4
    heads * 16 * 4 bytes; Eq. 3 reads 3 rows of 128 f32 and writes 3 * 4
    scores and ids."""
    assert work_lm.decode_step_bytes(LM, 3, 40, 4) == \
        90_112 * 4 + 40 * 2 * 2 * 4 * 16 * 4 + 3 * (128 * 4 + 4 * 8)
    ops, nbytes = work_lm.decode_step(LM, 3, 40, 4)
    assert nbytes == 403_040
    assert ops == 3 * (2 * 90_112 + 512 * 3) + 4 * 40 * 4 * 16 * 2
    bf16 = dict(LM, compute_dtype="bfloat16")
    assert work_lm.decode_step_bytes(bf16, 3, 40, 4) == \
        (90_112 + 40 * 2 * 2 * 4 * 16) * 2 + 3 * (128 * 4 + 4 * 8)


def _ctx(pd, config=LM, traffic=TRAFFIC["chat"]):
    spans, _ = _program.program_spans(pd)
    return types.SimpleNamespace(trace=trace.reduce(pd), program_spans=spans,
                                 config=config, traffic=traffic,
                                 peak=peaks.PEAKS["TPU v5e"])


def _request(rid, t):
    """One request's host spans from ``t`` (ns): a prefill of 100 holding
    the prompt's upload (4), three launches (5, 20, 6) and the first
    token's copy (3); then its insert: the slot (2), the insert (7),
    three scalars (2 each) and the slot update (4)."""
    return [("repro.prefill", t, 100, [("rid", rid), ("items", 8)]),
            ("repro.h2d", t + 5, 4, [("what", "prompt"), ("bytes", 32)]),
            ("repro.launch", t + 10, 5, [("fn", "expand"), ("rid", rid)]),
            ("repro.launch", t + 20, 20, [("fn", "prefill"), ("rid", rid)]),
            ("repro.launch", t + 50, 6, [("fn", "recover"), ("rid", rid)]),
            ("repro.d2h", t + 60, 3, [("what", "first"), ("bytes", 16)]),
            ("repro.h2d", t + 110, 2, [("what", "slot"), ("bytes", 4)]),
            ("repro.launch", t + 120, 7, [("fn", "insert"), ("rid", rid)]),
            ("repro.h2d", t + 130, 2, [("what", "slot"), ("bytes", 4)]),
            ("repro.h2d", t + 135, 2, [("what", "token"), ("bytes", 4)]),
            ("repro.h2d", t + 140, 2, [("what", "pos"), ("bytes", 4)]),
            ("repro.launch", t + 145, 4, [("fn", "set_slot"),
                                          ("rid", rid)])]


def _step(t, live):
    """One decode step from ``t``: four launches (8, 2, 3, 2), the wait
    (50) and the ids' copy (3)."""
    return [("repro.launch", t, 8, [("fn", "decode"), ("live", live)]),
            ("repro.launch", t + 10, 2, [("fn", "slice_next"),
                                         ("live", live)]),
            ("repro.launch", t + 15, 3, [("fn", "advance"), ("live", live)]),
            ("repro.launch", t + 20, 2, [("fn", "slice_top1"),
                                         ("live", live)]),
            ("repro.wait", t + 25, 50, [("live", live)]),
            ("repro.d2h", t + 80, 3, [("what", "ids"), ("bytes", 16)])]


def test_span_readers_count_tokens_launches_and_transfers():
    # two requests, then two steps of 2 and 3 live rows; the first
    # request lies before the window, so its spans do not count
    host = ([("bench.window", 150, 10_000)] + _request(0, 0)
            + _request(1, 200) + _step(400, 2) + _step(500, 3))
    got = {n: _reader(n).read(_ctx(_pd({}, host))) for n in SPAN_READERS}
    tokens = 1 + 2 + 3
    assert got["launches_per_token.lm"] == pytest.approx((5 + 2 * 4) / tokens)
    transfer_ns = (4 + 3 + 4 * 2) + 2 * 3
    assert got["transfer_ms_per_token.lm"] == pytest.approx(
        transfer_ns / tokens / 1e6)
    assert got["step_wait_ms.lm"] == pytest.approx(50 / 1e6)


@pytest.mark.parametrize("prefill_span", [False, True])
def test_span_readers_read_nothing_without_the_programs_spans(prefill_span):
    """The parent of the LM spans emits no ``repro.*`` span on the LM
    path, though its prefill pool spans each request
    (``repro.prefill``): the span readers report nothing, and raise
    nothing."""
    host = [("bench.window", 0, 1000),
            ("bench.prefill", 10, 100, [("n", 1), ("tokens", 8),
                                        ("tokens_sq", 64)]),
            ("bench.step", 300, 100, [("live", 2), ("keys", 30)])]
    if prefill_span:
        host.append(("repro.prefill", 20, 80, [("rid", 0), ("items", 8)]))
    pd = _pd({"XLA Modules": [("jit_step(1)", 320, 40)]}, host)
    assert {n: _reader(n).read(_ctx(pd)) for n in SPAN_READERS} == \
        dict.fromkeys(SPAN_READERS)


def test_decode_step_roofline_on_a_known_trace():
    """Two steps, (3 live, 40 keys) and (2 live, 25 keys), whose
    programs run 1,000 and 600 ns: the least time of each is its bytes
    over 819 GB/s (the tiny step is bound by memory), and their sum is
    taken over 1,600 ns.  A trace without device programs reads None."""
    host = [("bench.window", 0, 10_000),
            ("bench.step", 100, 1_500, [("live", 3), ("keys", 40)]),
            ("bench.step", 2_000, 1_000, [("live", 2), ("keys", 25)])]
    dev = {"XLA Modules": [("jit_step(1)", 200, 1_000),
                           ("jit_step(1)", 2_100, 600)]}
    got = _reader("decode_step_roofline.lm").read(_ctx(_pd(dev, host)))
    t_min = sum(work_lm.decode_step_bytes(LM, live, keys, 4) / 819e9
                for live, keys in ((3, 40), (2, 25)))
    assert got == pytest.approx(100 * t_min / 1_600e-9)
    assert 0 < got < 100
    assert _reader("decode_step_roofline.lm").read(
        _ctx(_pd({}, host))) is None


def test_traced_chat_cell_reads_the_span_metrics(tiny_root, capsys):
    """``run.main`` with ``--trace 1`` on the tiny chat cell on the CPU:
    the span readers find the LM program's spans in the run's trace.  A
    CPU trace has no TPU plane, so the decode step's roofline, a device
    metric, reads nothing there."""
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["per_layer"] += [{"name": n, "unit": u, "workloads": ["tiny.chat"]}
                          for n, u in zip(READERS, UNITS)]
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    rc = run.main(["--workload", "tiny.chat", "--seed", str(2**31 + 21),
                   "--seconds", "2", "--trace", "1"], root=tiny_root)
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True
    got = {n: res["metrics"].get(n, {}).get("value") for n in READERS}
    # at most 4 rows a step: at least a launch a token for each step's
    # four, and at most those and a request's six for each token
    assert 1 <= got["launches_per_token.lm"] <= 4 + 6
    assert got["transfer_ms_per_token.lm"] > 0
    assert got["step_wait_ms.lm"] > 0
    assert got["decode_step_roofline.lm"] is None


# -- the program through its decode cache against the reference --------

# float32 program and reference differ by the order of their sums: the
# served Eq. 3 scores read 2.9e-6 from the reference's at this size
# (CPU); a bfloat16 cache moves them by 1.4e-3.  Neither flips a token
# that is not tied to 1e-6, so ``token_gap`` alone cannot see the cache's
# precision here, and the scores are compared beside it.
SCORE_TOL = 1e-4


def _serve_through_the_cache(cache_dtype, monkeypatch):
    """Four requests through the cell's system (``PrefillPool``, the
    ``LMSlotProgram`` prefill and insert, decode steps through the slot
    cache), with the pool's cache in ``cache_dtype``; returns the
    requests and, per request, the served top-k (ids, scores) of each
    decode step."""
    from repro.models import transformer as tf
    from repro.serving.scheduler import Request, ServeStats
    init = tf.init_lm_cache
    monkeypatch.setattr(tf, "init_lm_cache", lambda *a, **kw: init(
        *a, **dict(kw, dtype=cache_dtype)))
    traffic = TRAFFIC["chat"]
    sut = lm.System(LM, traffic, SEED)
    program = sut.program
    decode, seen = program._stage_decodes[0], []

    def spy(*args):
        out = decode(*args)
        seen.append((np.asarray(out["topk_ids"]),
                     np.asarray(out["topk_scores"])))
        return out

    program._stage_decodes[0] = spy
    state = program.init_state(sut.n_slots)
    rng = np.random.default_rng(0)
    reqs = []
    for slot, (L, gen) in enumerate([(16, 6), (8, 6), (16, 4), (8, 6)]):
        r = Request(rid=slot, prompt=rng.integers(
            0, LM["vocab_size"], L).astype(np.int32), max_gen=gen)
        r.slot = slot
        reqs.append(r)
    stats = ServeStats()
    live = {r.slot: r for r, res in zip(reqs, sut.pool.prefill_all(reqs))
            if program.insert(state, r, res, stats)}
    rows = {r.rid: [] for r in reqs}
    while live:
        out = program.step(sut.params, state)
        ids, scores = seen[-1]
        for slot, r in list(live.items()):
            rows[r.rid].append((ids[slot], scores[slot]))
            if program.emit(state, r, slot, out, stats):
                del live[slot]
    sut.release()
    return reqs, rows


@pytest.mark.parametrize("cache_dtype,agrees", [(jnp.float32, True),
                                                (jnp.bfloat16, False)])
def test_served_through_the_cache_agrees_with_the_reference(
        cache_dtype, agrees, monkeypatch):
    reqs, rows = _serve_through_the_cache(cache_dtype, monkeypatch)
    mc = lm.program_config(LM)
    params = lm.reference_params(LM, mc, SEED, serve=True)
    gap, _ = lm.token_gaps(LM, mc, params, reqs, TRAFFIC["chat"]["max_len"],
                           max(TRAFFIC["chat"]["output_lens"]))
    assert gap <= LM["limits"]["token_gap"]
    err = 0.0
    for r in reqs:
        assert len(rows[r.rid]) == r.max_gen - 1
        seq = jnp.asarray(np.concatenate(
            [r.prompt, np.asarray(r.tokens[:-1], np.int32)]))
        logp = jax.nn.log_softmax(ref_lm.forward(params, seq, LM), -1)
        for j, (ids, scores) in enumerate(rows[r.rid]):
            # step j fed token j, at position prompt_len + j, and
            # recovered token j + 1: the reference's row there, scored
            # over the same ids
            want = ref_lm._item_scores(
                logp[r.prompt_len + j][:, None], jnp.asarray(ids), LM)[:, 0]
            err = max(err, float(np.abs(np.asarray(want) - scores).max()))
            assert ids[0] == r.tokens[j + 1]
    assert (err <= SCORE_TOL) == agrees, err
