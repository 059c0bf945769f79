"""LM assembly across families: fwd/train/prefill/decode consistency."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import (BloomConfig, MambaConfig, MoEConfig,
                                ModelConfig)
from repro.models import encdec, transformer as tf

KEY = jax.random.PRNGKey(0)


def _dense_cfg(**kw):
    base = dict(name="t", num_layers=2, d_model=32, num_heads=4,
                num_kv_heads=2, head_dim=8, d_ff=64, vocab=128,
                dtype="float32", attn_chunk_q=8, attn_chunk_k=8,
                bloom=BloomConfig(enabled=True, m_ratio=0.5, k=3))
    base.update(kw)
    return ModelConfig(**base)


def test_scan_equals_unrolled_layers():
    cfg_scan = _dense_cfg(scan_layers=True)
    cfg_un = _dense_cfg(scan_layers=False)
    params = tf.lm_init(KEY, cfg_scan)
    toks = jax.random.randint(KEY, (2, 8), 0, 128)
    o1 = tf.lm_apply(params, cfg_scan, {"tokens": toks})["logits"]
    o2 = tf.lm_apply(params, cfg_un, {"tokens": toks})["logits"]
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


def test_remat_equals_no_remat():
    cfg_a = _dense_cfg(remat="full")
    cfg_b = _dense_cfg(remat="none")
    params = tf.lm_init(KEY, cfg_a)
    toks = jax.random.randint(KEY, (2, 8), 0, 128)
    la, _ = tf.lm_loss_fn(params, cfg_a, {"tokens": toks})
    lb, _ = tf.lm_loss_fn(params, cfg_b, {"tokens": toks})
    assert float(la) == pytest.approx(float(lb), rel=1e-6)
    ga = jax.grad(lambda p: tf.lm_loss_fn(p, cfg_a, {"tokens": toks})[0])(
        params)
    gb = jax.grad(lambda p: tf.lm_loss_fn(p, cfg_b, {"tokens": toks})[0])(
        params)
    na = float(jnp.linalg.norm(ga["io"]["embed"]))
    nb = float(jnp.linalg.norm(gb["io"]["embed"]))
    assert na == pytest.approx(nb, rel=1e-4)


def test_prefill_then_decode_matches_full_forward():
    """logits(prefill tokens[:-1]) + decode(tokens[-1]) must equal the full
    forward — the serving path is numerically the training path."""
    cfg = _dense_cfg()
    params = tf.lm_init(KEY, cfg)
    S = 8
    toks = jax.random.randint(KEY, (2, S), 0, 128)
    full = tf.lm_apply(params, cfg, {"tokens": toks})["logits"]

    pre = tf.lm_apply(params, cfg, {"tokens": toks[:, :S - 1]},
                      mode="prefill")
    caches = tf.init_lm_cache(cfg, 2, S, dtype=jnp.float32)
    small = pre["caches"]

    def put(buf, sm):
        sl = tuple(slice(0, s) for s in sm.shape)
        return buf.at[sl].set(sm.astype(buf.dtype))

    caches = jax.tree.map(put, caches, small)
    dec = tf.lm_apply(params, cfg, {"tokens": toks[:, S - 1:]},
                      mode="decode", caches=caches,
                      pos=jnp.int32(S - 1))
    np.testing.assert_allclose(np.asarray(dec["logits"][:, 0]),
                               np.asarray(full[:, -1]), atol=5e-4)
    # prefill logits also match the full forward prefix
    np.testing.assert_allclose(np.asarray(pre["logits"]),
                               np.asarray(full[:, :S - 1]), atol=5e-4)


@pytest.mark.parametrize("arch", list(configs.ARCH_NAMES))
def test_prefill_decode_consistency_all_archs(arch):
    """Same consistency check across every assigned architecture family."""
    cfg = configs.get_smoke_config(arch, dtype="float32")
    S = 16
    toks = jax.random.randint(KEY, (2, S), 0, cfg.vocab)
    if cfg.family == "audio":
        emb = jax.random.normal(KEY, (2, 8, cfg.d_model))
        full = encdec.encdec_apply(params := encdec.encdec_init(KEY, cfg),
                                   cfg, {"tokens": toks, "embeds": emb}
                                   )["logits"]
        pre = encdec.encdec_apply(params, cfg,
                                  {"tokens": toks[:, :S - 1],
                                   "embeds": emb}, mode="prefill")
        caches = encdec.init_encdec_cache(cfg, 2, S, 8, dtype=jnp.float32)
        apply_decode = lambda c: encdec.encdec_apply(  # noqa: E731
            params, cfg, {"tokens": toks[:, S - 1:]}, mode="decode",
            caches=c, pos=jnp.int32(S - 1))
    else:
        params = tf.lm_init(KEY, cfg)
        batch = {"tokens": toks}
        if cfg.family == "vlm":
            batch["embeds"] = jax.random.normal(KEY, (2, 4, cfg.d_model))
        full = tf.lm_apply(params, cfg, batch)["logits"]
        pre_batch = dict(batch, tokens=toks[:, :S - 1])
        pre = tf.lm_apply(params, cfg, pre_batch, mode="prefill")
        caches = tf.init_lm_cache(cfg, 2, S + 4, dtype=jnp.float32)
        apply_decode = lambda c: tf.lm_apply(  # noqa: E731
            params, cfg, {"tokens": toks[:, S - 1:]}, mode="decode",
            caches=c, pos=jnp.int32(full.shape[1] - 1))

    def put(buf, sm):
        sl = tuple(slice(0, s) for s in sm.shape)
        return buf.at[sl].set(sm.astype(buf.dtype))

    caches = jax.tree.map(put, caches, pre["caches"])
    dec = apply_decode(caches)
    assert np.isfinite(np.asarray(dec["logits"])).all()
    np.testing.assert_allclose(np.asarray(dec["logits"][:, 0]),
                               np.asarray(full[:, -1]), atol=3e-3)


def test_vlm_frontend_prefix_changes_logits():
    cfg = _dense_cfg(family="vlm", frontend="vision_stub")
    params = tf.lm_init(KEY, cfg)
    toks = jax.random.randint(KEY, (1, 6), 0, 128)
    e1 = jax.random.normal(KEY, (1, 4, 32))
    e2 = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 4, 32))
    o1 = tf.lm_apply(params, cfg, {"tokens": toks, "embeds": e1})["logits"]
    o2 = tf.lm_apply(params, cfg, {"tokens": toks, "embeds": e2})["logits"]
    assert o1.shape[1] == 10  # 4 patches + 6 tokens
    assert float(jnp.abs(o1 - o2).max()) > 1e-6


def test_loss_mask_respected():
    cfg = _dense_cfg()
    params = tf.lm_init(KEY, cfg)
    toks = jax.random.randint(KEY, (2, 8), 0, 128)
    mask = jnp.zeros((2, 7))
    loss, _ = tf.lm_loss_fn(params, cfg,
                            {"tokens": toks, "loss_mask": mask})
    assert float(loss) == 0.0


def test_dense_io_vs_bloom_io_shapes():
    for bloom in (True, False):
        cfg = _dense_cfg(bloom=BloomConfig(enabled=bloom, m_ratio=0.5, k=3))
        params = tf.lm_init(KEY, cfg)
        toks = jax.random.randint(KEY, (1, 4), 0, 128)
        logits = tf.lm_apply(params, cfg, {"tokens": toks})["logits"]
        assert logits.shape[-1] == (64 if bloom else 128)


POOL_SLOTS, POOL_LEN = 4, 32


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "qwen3-4b", "mamba2-1.3b"])
@pytest.mark.parametrize("case", ["staggered", "reused_slot"])
def test_inplace_pool_decode_matches_masked(arch, case):
    """The slot-pool decode on one device writes each slot's new KV row
    in place (``kv_write`` "inplace"); under a ``dist`` (here a
    one-device mesh) the same step rewrites the scanned caches by an
    ``iota == pos`` select ("masked").  Step after step, with slots at
    different positions, both give the same logits and caches.  In
    ``reused_slot`` a slot is then re-admitted with a shorter prompt, so
    its old occupant's positions lie past the new prompt: the slot's
    logits equal those of the same request in a fresh pool, so that
    stale tail is never read."""
    from repro.launch import steps as steps_lib
    from repro.launch.mesh import auto_mesh
    from repro.launch.sharding import DistContext
    cfg = configs.get_smoke_config(arch)
    params = tf.lm_init(KEY, cfg)
    dist = DistContext(auto_mesh((1, 1), ("data", "model"),
                                 devices=jax.devices()[:1]))
    paths = {"inplace": None, "masked": dist}
    assert {w: steps_lib.make_slot_decode_step(cfg, dist=d).kv_write
            for w, d in paths.items()} == {w: w for w in paths}
    decode = {w: jax.jit(lambda c, tok, pos, d=d: tf.lm_apply(
        params, cfg, {"tokens": tok}, mode="decode", caches=c, pos=pos,
        dist=d)) for w, d in paths.items()}
    prefill = jax.jit(lambda t: tf.lm_apply(params, cfg, {"tokens": t},
                                            mode="prefill")["caches"])
    insert = jax.jit(steps_lib.insert_cache_slot)
    rng = np.random.default_rng(0)

    def fresh():
        return tf.init_lm_cache(cfg, POOL_SLOTS, POOL_LEN, dtype=jnp.float32)

    def admit(pool, slot, prompt):
        return insert(pool, prefill(jnp.asarray(prompt)[None]), slot)

    def steps(pools, pos, n):
        """n decode steps of every pool from positions ``pos``; the
        logits of each step, per pool."""
        logits = {w: [] for w in pools}
        for i in range(n):
            tok = jnp.asarray(rng.integers(0, cfg.vocab, (POOL_SLOTS, 1)),
                              jnp.int32)
            p = jnp.asarray(pos + i, jnp.int32)
            for w in pools:
                out = decode.get(w, decode["inplace"])(pools[w], tok, p)
                pools[w] = out["caches"]
                logits[w].append(np.asarray(out["logits"]))
        return logits

    def assert_paths_agree(pools, logits):
        np.testing.assert_array_equal(logits["inplace"], logits["masked"])
        for a, b in zip(jax.tree.leaves(pools["inplace"]),
                        jax.tree.leaves(pools["masked"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    lens = np.array([5, 13, 3, 9])
    pools = {w: fresh() for w in paths}
    for slot, L in enumerate(lens):
        prompt = rng.integers(0, cfg.vocab, L)
        pools = {w: admit(p, slot, prompt) for w, p in pools.items()}
    assert_paths_agree(pools, steps(pools, lens, 4))
    if case == "staggered":
        return

    slot, short = 1, rng.integers(0, cfg.vocab, 4)
    assert len(short) < lens[slot] + 4
    pools = {w: admit(p, slot, short) for w, p in pools.items()}
    pools["alone"] = admit(fresh(), slot, short)
    pos = lens + 4
    pos[slot] = len(short)
    logits = steps(pools, pos, 4)
    assert_paths_agree(pools, logits)
    np.testing.assert_array_equal(np.asarray(logits["alone"])[:, slot],
                                  np.asarray(logits["inplace"])[:, slot])
