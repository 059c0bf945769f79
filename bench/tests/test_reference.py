"""The plain references against the program at smoke sizes on the CPU
(float32 throughout, so they agree to rounding), and the control: the
reference path one precision lower (bfloat16 for the smoke presets'
float32) fails the comparison."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import bloom_hash, lm as ref_lm, retrieval as ref_ret
from bench.systems import lm, retrieval
from bench.tests.conftest import LM, RETRIEVAL, TRAFFIC

SEED = 2**33 + 17            # a seed above 32 bits


def test_hash_matches_the_program_definition():
    from repro.core.hashing import double_hash
    ids = jnp.asarray([0, 1, 2, 151935, 9_999_999, 123456], jnp.int32)
    for k, m, seed in [(4, 30208, 0), (2, 8192, 0), (3, 128, 5)]:
        np.testing.assert_array_equal(
            np.asarray(bloom_hash.indices(ids, k=k, m=m, seed=seed)),
            np.asarray(double_hash(ids, k, m, seed)))


def _retrieval_answers(cfg, table_dtype=None):
    rcfg = retrieval.program_config(cfg, table_dtype)
    tower = retrieval.make_tower(cfg, SEED)
    from repro.launch import steps as steps_lib
    items = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg["d"], size=(6, cfg["c_max"])), jnp.int32)
    logits = jax.jit(steps_lib.make_retrieval_prefill_step(rcfg))(
        tower, items)
    vals, ids = jax.jit(steps_lib.make_retrieval_decode_step(rcfg))(
        logits, jnp.ones((6,), bool))
    return tower, items, np.asarray(vals), np.asarray(ids)


def test_retrieval_reference_agrees_with_the_program():
    cfg = RETRIEVAL
    tower, items, vals, ids = _retrieval_answers(cfg)
    kw = dict(m=cfg["m"], k=cfg["k"], seed=cfg["hash_seed"])
    logp = ref_ret.log_probs([(t["w"], t["b"]) for t in tower.values()],
                             items, **kw)
    best = ref_ret.topk_values(logp, d=cfg["d"], topk=cfg["topk"],
                               block=1 << 14, **kw)
    np.testing.assert_allclose(np.asarray(best), vals, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(ref_ret.scores_of(logp, jnp.asarray(ids), **kw)), vals,
        atol=1e-5)


def _served(items, ids, vals):
    from repro.serving.scheduler import Request
    out = []
    for i in range(len(ids)):
        r = Request(rid=i, prompt=np.asarray(items[i]), max_gen=1,
                    kind="oneshot")
        r.topk_ids, r.topk_scores = list(map(int, ids[i])), \
            list(map(float, vals[i]))
        out.append(r)
    return out


def test_retrieval_check_passes_the_program_and_fails_bf16():
    traffic = dict(TRAFFIC["zipf"], check_sample=6)
    _, items, vals, ids = _retrieval_answers(RETRIEVAL)
    ok = dict(retrieval.check(RETRIEVAL, traffic, SEED,
                              _served(items, ids, vals)))
    assert all(v <= RETRIEVAL["limits"][k] for k, v in ok.items()), ok
    _, items, vals, ids = _retrieval_answers(RETRIEVAL, "bfloat16")
    bad = dict(retrieval.check(RETRIEVAL, traffic, SEED,
                               _served(items, ids, vals)))
    assert any(v > RETRIEVAL["limits"][k] for k, v in bad.items()), bad


@pytest.mark.parametrize("quant", [None, jnp.bfloat16])
def test_lm_reference_forward_against_the_program(quant):
    from repro.launch import steps as steps_lib
    mc = lm.program_config(LM)
    params = lm.make_params(LM, mc, SEED, serve=True)
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, LM["vocab_size"], size=24), jnp.int32)
    prog = jax.jit(steps_lib.make_prefill_step(mc))
    want = np.asarray(jax.vmap(lambda t: prog(params, {"tokens": t[None]})[
        "last_logits"][0])(jnp.stack([tokens[:n] for n in (24,)])))
    got = np.asarray(ref_lm.forward(lm.reference_params(LM, mc, SEED, True),
                                    tokens, LM, quant=quant))[-1:]
    err = np.abs(got - want).max()
    if quant is None:
        assert err < 1e-4
    else:
        assert err > 1e-3


def test_lm_recovery_gaps_read_zero_on_the_best_items():
    cfg = LM
    logp = jax.nn.log_softmax(jax.random.normal(
        jax.random.PRNGKey(0), (5, cfg["bloom_m"])), -1)
    ids = jnp.arange(cfg["vocab_size"])
    scores = ref_lm._item_scores(logp.T, ids, cfg)          # (V, P)
    best = jnp.argmax(scores, axis=0)
    gap, gap_alt = ref_lm.recovery_gaps(logp, logp, best, cfg, chunk=100)
    np.testing.assert_allclose(np.asarray(gap), 0.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gap_alt), 0.0, atol=1e-6)
    worst = jnp.argmin(scores, axis=0)
    gap, _ = ref_lm.recovery_gaps(logp, None, worst, cfg)
    np.testing.assert_allclose(
        np.asarray(gap), np.asarray(scores.max(0) - scores.min(0)),
        rtol=1e-5)
