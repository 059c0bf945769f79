"""Pallas kernels vs ref.py oracles: shape/dtype sweeps and custom-VJP
gradient checks (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bloom import BloomSpec
from repro.kernels import ops, ref
from repro.kernels.bloom_ce import bloom_ce_pallas
from repro.kernels.bloom_decode import bloom_decode_pallas
from repro.kernels.bloom_decode_topk import bloom_decode_topk_pallas
from repro.kernels.bloom_embed import bloom_embed_pallas

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("T,k,m,D", [
    (1, 1, 16, 32), (7, 3, 64, 48), (32, 4, 128, 256), (13, 8, 256, 100),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bloom_embed_sweep(T, k, m, D, dtype):
    table = jax.random.normal(KEY, (m, D), dtype)
    idx = jax.random.randint(jax.random.fold_in(KEY, 1), (T, k), 0, m)
    got = bloom_embed_pallas(table, idx, d_tile=64, interpret=True)
    want = ref.bloom_embed_ref(table, idx)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-6,
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-6)


@pytest.mark.parametrize("B,m,d,k", [
    (1, 32, 100, 1), (5, 64, 333, 3), (8, 128, 1024, 4), (3, 96, 50, 2),
])
def test_bloom_decode_sweep(B, m, d, k):
    logp = jax.nn.log_softmax(jax.random.normal(KEY, (B, m)))
    H = jax.random.randint(jax.random.fold_in(KEY, 2), (d, k), 0, m)
    got = bloom_decode_pallas(logp, H, b_tile=4, v_tile=64, interpret=True)
    want = ref.bloom_decode_ref(logp, H)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("T,m,k", [
    (1, 16, 1), (9, 64, 4), (32, 128, 3), (17, 256, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bloom_ce_sweep(T, m, k, dtype):
    z = jax.random.normal(KEY, (T, m), dtype)
    h = jax.random.randint(jax.random.fold_in(KEY, 3), (T, k), 0, m)
    got = bloom_ce_pallas(z, h, t_tile=4, interpret=True)
    want = ref.bloom_ce_ref(z, h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=2e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_ops_match_model_layer_oracles():
    """kernels.ops wrappers == repro.core jnp implementations end to end."""
    from repro.core import losses
    from repro.core.bloom import decode_scores
    spec = BloomSpec(d=500, m=128, k=4, seed=3)
    table = jax.random.normal(KEY, (128, 64))
    tokens = jax.random.randint(KEY, (2, 5), 0, 500)

    got = ops.bloom_embed(table, tokens, spec)
    idx = spec.indices_for(tokens)
    want = jnp.take(table, idx, axis=0).sum(axis=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5)

    logits = jax.random.normal(KEY, (2, 5, 128))
    labels = jax.random.randint(KEY, (2, 5), 0, 500)
    got = ops.bloom_ce(logits, labels, spec)
    want = losses.bloom_xent_label(spec, logits, labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

    logp = jax.nn.log_softmax(jax.random.normal(KEY, (3, 128)))
    got = ops.bloom_decode(logp, spec)
    want = decode_scores(spec, logp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("B,m,d,k,topk", [
    (1, 32, 100, 1, 1), (5, 64, 333, 3, 8), (8, 128, 1024, 4, 16),
    (3, 96, 50, 2, 50),   # topk == d: full sort equivalence
])
def test_bloom_decode_topk_sweep(B, m, d, k, topk):
    """Fused streaming decode-topk == decode-then-top_k, without the (B, d)
    intermediate."""
    logp = jax.nn.log_softmax(jax.random.normal(KEY, (B, m)))
    H = jax.random.randint(jax.random.fold_in(KEY, 2), (d, k), 0, m)
    vals, ids = bloom_decode_topk_pallas(logp, H, topk, b_tile=4, v_tile=64,
                                         interpret=True)
    scores = ref.bloom_decode_ref(logp, H)
    want_v, _ = jax.lax.top_k(scores, topk)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(want_v),
                               rtol=1e-5, atol=1e-5)
    # ids must point at rows achieving those scores (ties may permute ids)
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    np.testing.assert_allclose(np.asarray(picked), np.asarray(want_v),
                               rtol=1e-5, atol=1e-5)
    assert int(ids.min()) >= 0 and int(ids.max()) < d


def test_bloom_decode_topk_masked_vocab_never_yields_sentinel_ids():
    """-inf log-probs (masked vocab) must yield real vocab ids and the same
    lowest-index tie ordering as decode-then-top_k — no -1 sentinels."""
    B, m, d, k, topk = 3, 32, 300, 2, 8
    logp = jax.nn.log_softmax(jax.random.normal(KEY, (B, m)))
    # mask most of the m-space: the vast majority of Eq. 3 scores hit -inf
    logp = logp.at[:, 4:].set(-jnp.inf)
    H = jax.random.randint(jax.random.fold_in(KEY, 2), (d, k), 0, m)
    vals, ids = bloom_decode_topk_pallas(logp, H, topk, b_tile=2, v_tile=64,
                                         interpret=True)
    scores = ref.bloom_decode_ref(logp, H)
    want_v, want_i = jax.lax.top_k(scores, topk)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_i))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(want_v))
    assert int(ids.min()) >= 0


@pytest.mark.parametrize("occupancy", [1 / 8, 1 / 2, 1.0])
@pytest.mark.parametrize("b_tile", [1, 4])
def test_bloom_decode_topk_row_skipping_matches_dense(occupancy, b_tile):
    """The slot-occupancy-prefetched grid == the dense grid on every row
    block containing a live slot, and (-inf, 0) on fully-dead blocks —
    exactly the post-hoc masking recover_topk applies (DESIGN.md §8).
    With b_tile=1 that is per-slot-row skipping."""
    B, m, d, k, topk = 8, 64, 333, 3, 5
    logp = jax.nn.log_softmax(jax.random.normal(KEY, (B, m)))
    H = jax.random.randint(jax.random.fold_in(KEY, 2), (d, k), 0, m)
    active = np.zeros(B, bool)
    active[:max(1, int(B * occupancy))] = True

    vals, ids = bloom_decode_topk_pallas(
        logp, H, topk, b_tile=b_tile, v_tile=64, interpret=True,
        active=jnp.asarray(active))
    dense_v, dense_i = bloom_decode_topk_pallas(
        logp, H, topk, b_tile=b_tile, v_tile=64, interpret=True)

    live_block = active.reshape(-1, b_tile).any(axis=1).repeat(b_tile)
    np.testing.assert_array_equal(np.asarray(vals)[live_block],
                                  np.asarray(dense_v)[live_block])
    np.testing.assert_array_equal(np.asarray(ids)[live_block],
                                  np.asarray(dense_i)[live_block])
    assert np.all(np.asarray(vals)[~live_block] == -np.inf)
    assert np.all(np.asarray(ids)[~live_block] == 0)


def test_bloom_decode_topk_row_skipping_scattered_occupancy():
    """Non-contiguous live slots (the realistic mid-flight pool): blocks
    are skipped wherever a whole b_tile of slots drained, and the pinned
    logp/H index maps never corrupt a later live block's output."""
    B, m, d, k, topk = 12, 48, 257, 2, 4
    logp = jax.nn.log_softmax(jax.random.normal(KEY, (B, m)))
    H = jax.random.randint(jax.random.fold_in(KEY, 3), (d, k), 0, m)
    # live, dead, dead, live blocks at b_tile=3
    active = np.array([True, False, True,
                       False, False, False,
                       False, False, False,
                       False, True, False])
    vals, ids = bloom_decode_topk_pallas(
        logp, H, topk, b_tile=3, v_tile=64, interpret=True,
        active=jnp.asarray(active))
    dense_v, dense_i = bloom_decode_topk_pallas(
        logp, H, topk, b_tile=3, v_tile=64, interpret=True)
    live_block = active.reshape(-1, 3).any(axis=1).repeat(3)
    np.testing.assert_array_equal(np.asarray(vals)[live_block],
                                  np.asarray(dense_v)[live_block])
    np.testing.assert_array_equal(np.asarray(ids)[live_block],
                                  np.asarray(dense_i)[live_block])
    assert np.all(np.asarray(vals)[~live_block] == -np.inf)

    # leading dead blocks (low slots drained first — forward pin path):
    # only the LAST block is live
    active2 = np.zeros(B, bool)
    active2[-2] = True
    vals2, ids2 = bloom_decode_topk_pallas(
        logp, H, topk, b_tile=3, v_tile=64, interpret=True,
        active=jnp.asarray(active2))
    np.testing.assert_array_equal(np.asarray(vals2)[-3:],
                                  np.asarray(dense_v)[-3:])
    np.testing.assert_array_equal(np.asarray(ids2)[-3:],
                                  np.asarray(dense_i)[-3:])
    assert np.all(np.asarray(vals2)[:-3] == -np.inf)
    assert np.all(np.asarray(ids2)[:-3] == 0)


def test_recover_topk_active_mask_drives_row_skipping_kernel():
    """io.recover_topk(active=...) on the pallas path returns the same
    (scores, ids) as the xla path with the same mask — the kernel-level
    block skipping composes with the row-level post-mask."""
    import dataclasses
    from repro import configs
    from repro.models import io as io_lib

    cfg = configs.get_smoke_config("qwen1.5-0.5b")
    B = 6
    logits = jax.random.normal(KEY, (B, cfg.m_vocab))
    active = jnp.asarray(np.array([True, False, True, False, False, True]))
    cfg_x = dataclasses.replace(cfg, io_impl="xla")
    cfg_p = dataclasses.replace(cfg, io_impl="pallas")
    sx, ix = io_lib.recover_topk(cfg_x, logits, topk=4, active=active)
    sp, ip = io_lib.recover_topk(cfg_p, logits, topk=4, active=active)
    np.testing.assert_allclose(np.asarray(sx), np.asarray(sp),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(ix), np.asarray(ip))
    assert np.all(np.asarray(sp)[~np.asarray(active)] == -np.inf)
    assert np.all(np.asarray(ip)[~np.asarray(active)] == 0)


# --------------------------------------------------------------------------
# custom-VJP gradients vs the XLA oracles (acceptance: <= 1e-4 max abs err)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("T,k,m,D", [
    (1, 1, 16, 32), (7, 3, 64, 48), (32, 4, 128, 256), (13, 8, 256, 100),
])
def test_bloom_embed_grad(T, k, m, D):
    """Scatter-add backward kernel == XLA gather-sum gradient."""
    table = jax.random.normal(KEY, (m, D))
    idx = jax.random.randint(jax.random.fold_in(KEY, 1), (T, k), 0, m)
    cot = jax.random.normal(jax.random.fold_in(KEY, 9), (T, D))
    g_pal = jax.grad(lambda t: jnp.sum(
        bloom_embed_pallas(t, idx, d_tile=64, interpret=True) * cot))(table)
    g_ref = jax.grad(lambda t: jnp.sum(
        ref.bloom_embed_ref(t, idx) * cot))(table)
    np.testing.assert_allclose(np.asarray(g_pal), np.asarray(g_ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("T,m,k", [
    (1, 16, 1), (9, 64, 4), (32, 128, 3), (17, 256, 8),
])
def test_bloom_ce_grad(T, m, k):
    """lse-residual backward kernel == XLA softmax-CE gradient."""
    z = jax.random.normal(KEY, (T, m))
    h = jax.random.randint(jax.random.fold_in(KEY, 3), (T, k), 0, m)
    cot = jax.random.normal(jax.random.fold_in(KEY, 9), (T,))
    g_pal = jax.grad(lambda zz: jnp.sum(
        bloom_ce_pallas(zz, h, t_tile=4, interpret=True) * cot))(z)
    g_ref = jax.grad(lambda zz: jnp.sum(
        ref.bloom_ce_ref(zz, h) * cot))(z)
    np.testing.assert_allclose(np.asarray(g_pal), np.asarray(g_ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("B,m,d,k", [
    (1, 32, 100, 1), (5, 64, 333, 3), (8, 128, 1024, 4),
])
def test_bloom_decode_grad(B, m, d, k):
    """Blocked scatter-add backward kernel == XLA Eq. 3 gradient."""
    logp = jax.nn.log_softmax(jax.random.normal(KEY, (B, m)))
    H = jax.random.randint(jax.random.fold_in(KEY, 2), (d, k), 0, m)
    cot = jax.random.normal(jax.random.fold_in(KEY, 9), (B, d))
    g_pal = jax.grad(lambda lp: jnp.sum(
        bloom_decode_pallas(lp, H, b_tile=4, v_tile=64,
                            interpret=True) * cot))(logp)
    g_ref = jax.grad(lambda lp: jnp.sum(
        ref.bloom_decode_ref(lp, H) * cot))(logp)
    np.testing.assert_allclose(np.asarray(g_pal), np.asarray(g_ref),
                               atol=1e-4, rtol=1e-4)


# --------------------------------------------------------------------------
# CSR-binned backward (bwd_impl="csr") vs the XLA oracle AND the dense
# Pallas backward — uniform, collision-heavy (skewed-hash) and ragged
# (non-tile-multiple T / m) shapes, incl. the all-tokens-in-one-m-tile
# and empty-m-tile extremes (ISSUE 5)
# --------------------------------------------------------------------------

def _embed_grads(table, idx, cot, *, m_tile, e_tile):
    """(csr, dense, oracle) dtable gradients for one embed shape."""
    g_csr = jax.grad(lambda t: jnp.sum(
        bloom_embed_pallas(t, idx, d_tile=64, interpret=True,
                           bwd_impl="csr", m_tile=m_tile,
                           e_tile=e_tile) * cot))(table)
    g_dense = jax.grad(lambda t: jnp.sum(
        bloom_embed_pallas(t, idx, d_tile=64, interpret=True,
                           bwd_impl="dense", m_tile=m_tile) * cot))(table)
    g_ref = jax.grad(lambda t: jnp.sum(
        ref.bloom_embed_ref(t, idx) * cot))(table)
    return g_csr, g_dense, g_ref


@pytest.mark.parametrize("T,k,m,D,m_tile,e_tile", [
    (1, 1, 16, 32, 16, 4),      # single entry, single tile
    (7, 3, 60, 48, 16, 4),      # ragged m (not an m_tile multiple)
    (13, 8, 250, 100, 64, 128), # e_tile > per-segment entries, ragged m
    (32, 4, 128, 256, 32, 8),   # multi-tile segments
    (5, 2, 40, 20, 16, 3),      # non-power-of-two e_tile, ragged T
])
def test_bloom_embed_grad_csr_uniform(T, k, m, D, m_tile, e_tile):
    """CSR backward == oracle == dense backward on uniform hash draws."""
    table = jax.random.normal(KEY, (m, D))
    idx = jax.random.randint(jax.random.fold_in(KEY, 1), (T, k), 0, m)
    cot = jax.random.normal(jax.random.fold_in(KEY, 9), (T, D))
    g_csr, g_dense, g_ref = _embed_grads(table, idx, cot,
                                         m_tile=m_tile, e_tile=e_tile)
    np.testing.assert_allclose(np.asarray(g_csr), np.asarray(g_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(g_csr), np.asarray(g_dense),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("hot", [1, 3])
def test_bloom_embed_grad_csr_collision_heavy(hot):
    """Skewed-hash extreme: every entry collides into `hot` distinct
    indices of ONE m-tile — one long multi-tile segment, every other
    m-tile empty (the pad-tile path must still zero their blocks)."""
    T, k, m, D, m_tile, e_tile = 24, 4, 160, 64, 32, 8
    table = jax.random.normal(KEY, (m, D))
    idx = jax.random.randint(jax.random.fold_in(KEY, 2), (T, k), 0, hot)
    cot = jax.random.normal(jax.random.fold_in(KEY, 9), (T, D))
    g_csr, g_dense, g_ref = _embed_grads(table, idx, cot,
                                         m_tile=m_tile, e_tile=e_tile)
    np.testing.assert_allclose(np.asarray(g_csr), np.asarray(g_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(g_csr), np.asarray(g_dense),
                               atol=1e-4, rtol=1e-4)
    # rows the skew never touched must come back exactly zero
    assert np.all(np.asarray(g_csr)[hot:] == 0.0)


def test_bloom_embed_grad_csr_middle_tile_only():
    """Entries confined to a MIDDLE m-tile: leading and trailing m-tiles
    are both empty (exercises pad tiles on both sides of the live run)."""
    T, k, m, D, m_tile, e_tile = 9, 3, 96, 40, 32, 4
    table = jax.random.normal(KEY, (m, D))
    idx = 32 + jax.random.randint(jax.random.fold_in(KEY, 3), (T, k),
                                  0, 32)
    cot = jax.random.normal(jax.random.fold_in(KEY, 9), (T, D))
    g_csr, _, g_ref = _embed_grads(table, idx, cot,
                                   m_tile=m_tile, e_tile=e_tile)
    np.testing.assert_allclose(np.asarray(g_csr), np.asarray(g_ref),
                               atol=1e-4, rtol=1e-4)
    got = np.asarray(g_csr)
    assert np.all(got[:32] == 0.0) and np.all(got[64:] == 0.0)


@pytest.mark.parametrize("B,m,d,k,m_tile,e_tile", [
    (1, 32, 100, 1, 16, 8),
    (5, 64, 333, 3, 16, 32),    # ragged everything
    (8, 128, 1024, 4, 64, 128),
])
def test_bloom_decode_grad_csr(B, m, d, k, m_tile, e_tile):
    """CSR decode backward (shared row-scatter kernel on the transposed
    cotangent) == oracle == dense backward."""
    logp = jax.nn.log_softmax(jax.random.normal(KEY, (B, m)))
    H = jax.random.randint(jax.random.fold_in(KEY, 2), (d, k), 0, m)
    cot = jax.random.normal(jax.random.fold_in(KEY, 9), (B, d))

    def run(impl):
        return jax.grad(lambda lp: jnp.sum(
            bloom_decode_pallas(lp, H, b_tile=4, v_tile=64,
                                interpret=True, bwd_impl=impl,
                                m_tile=m_tile, e_tile=e_tile) * cot))(logp)

    g_csr, g_dense = run("csr"), run("dense")
    g_ref = jax.grad(lambda lp: jnp.sum(
        ref.bloom_decode_ref(lp, H) * cot))(logp)
    np.testing.assert_allclose(np.asarray(g_csr), np.asarray(g_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(g_csr), np.asarray(g_dense),
                               atol=1e-4, rtol=1e-4)


def test_bloom_decode_grad_csr_skewed_hash():
    """Collision-heavy H (whole vocab hashes into one m-tile)."""
    B, m, d, k, m_tile = 4, 96, 200, 3, 32
    logp = jax.nn.log_softmax(jax.random.normal(KEY, (B, m)))
    H = jax.random.randint(jax.random.fold_in(KEY, 5), (d, k), 0, 7)
    cot = jax.random.normal(jax.random.fold_in(KEY, 9), (B, d))
    g_csr = jax.grad(lambda lp: jnp.sum(
        bloom_decode_pallas(lp, H, b_tile=4, v_tile=64, interpret=True,
                            bwd_impl="csr", m_tile=m_tile,
                            e_tile=16) * cot))(logp)
    g_ref = jax.grad(lambda lp: jnp.sum(
        ref.bloom_decode_ref(lp, H) * cot))(logp)
    np.testing.assert_allclose(np.asarray(g_csr), np.asarray(g_ref),
                               atol=1e-4, rtol=1e-4)
    assert np.all(np.asarray(g_csr)[:, 7:] == 0.0)


def test_ops_decode_grad_uses_cached_bins():
    """ops.bloom_decode's csr path rides the per-spec cached bins thunk
    and still matches the XLA Eq. 3 gradient; forward-only calls never
    build the bins (the thunk resolves at backward-trace time only)."""
    from repro.core.bloom import cached_decode_bins, decode_scores
    from repro.kernels.bloom_csr import CSR_E_TILE
    from repro.kernels.common import BWD_M_TILE
    spec = BloomSpec(d=500, m=128, k=4, seed=3)
    logp = jax.nn.log_softmax(jax.random.normal(KEY, (3, 128)))
    cot = jax.random.normal(jax.random.fold_in(KEY, 9), (3, 500))

    # forward-only: no bins are built for a never-differentiated spec
    spec_fwd = BloomSpec(d=500, m=128, k=4, seed=4)
    hits0 = cached_decode_bins.cache_info().currsize
    ops.bloom_decode(logp, spec_fwd)
    assert cached_decode_bins.cache_info().currsize == hits0, \
        "forward-only bloom_decode must not pay the binning sort"

    # the hardest path: grad under an OUTER user jit — the bins thunk
    # resolves inside the backward trace, and both per-spec caches must
    # come out holding CONCRETE arrays (ensure_compile_time_eval), never
    # the outer trace's tracers
    g_csr = jax.grad(jax.jit(lambda lp: jnp.sum(
        ops.bloom_decode(lp, spec) * cot)))(logp)
    g_ref = jax.grad(lambda lp: jnp.sum(
        decode_scores(spec, lp) * cot))(logp)
    np.testing.assert_allclose(np.asarray(g_csr), np.asarray(g_ref),
                               atol=1e-4, rtol=1e-4)
    # the grad above populated the cache with concrete, eagerly-usable
    # arrays; hits return the same object
    b1 = cached_decode_bins(spec, BWD_M_TILE, CSR_E_TILE)
    b2 = cached_decode_bins(spec, BWD_M_TILE, CSR_E_TILE)
    assert b1.tok is b2.tok
    assert int(np.asarray(b1.tile_len).sum()) == spec.d * spec.k
    # and an eager (un-jitted) grad after the jitted one still works
    g_eager = jax.grad(lambda lp: jnp.sum(
        ops.bloom_decode(lp, spec) * cot))(logp)
    np.testing.assert_allclose(np.asarray(g_eager), np.asarray(g_ref),
                               atol=1e-4, rtol=1e-4)


def test_bin_csr_layout_invariants():
    """The binning pass is a permutation: every (source row, m index)
    entry lands in exactly one live slot of a tile owned by its m-block;
    tiles are sorted by block, every block owns >= 1 tile, and pad slots
    are sentinel-valued."""
    from repro.kernels.bloom_csr import bin_csr, csr_tile_counts
    T, k, m, m_tile, e_tile = 23, 5, 150, 32, 8
    idx = jax.random.randint(jax.random.fold_in(KEY, 7), (T, k), 0, m)
    bins = bin_csr(idx, m, m_tile=m_tile, e_tile=e_tile)
    nM, NT, et = csr_tile_counts(m, T * k, m_tile, e_tile)
    assert et == e_tile and bins.n_tiles == NT and bins.e_tile == e_tile

    tok = np.asarray(bins.tok)
    val = np.asarray(bins.val)[:, 0]
    tmb = np.asarray(bins.tile_mb)
    tfirst = np.asarray(bins.tile_first)
    tlen = np.asarray(bins.tile_len)

    # live (tok, val) pairs == the original (row, idx) entries, as multisets
    live = val >= 0
    got = sorted(zip(tok[live].tolist(), val[live].tolist()))
    want = sorted((t, int(v)) for t, row in enumerate(np.asarray(idx))
                  for v in row)
    assert got == want
    # tiles ascend by block; every block appears; first flags mark runs
    assert (np.diff(tmb) >= 0).all()
    assert set(range(nM)) <= set(tmb.tolist())
    assert tfirst.sum() == nM
    for t in range(NT):
        s = slice(t * e_tile, (t + 1) * e_tile)
        v = val[s]
        assert (v[:tlen[t]] >= 0).all()            # live prefix ...
        assert (v[tlen[t]:] == -1).all()           # ... then pad slots
        if tlen[t]:
            assert ((v[:tlen[t]] // m_tile) == tmb[t]).all()
    assert tlen.sum() == T * k


def test_bwd_impl_validation():
    table = jax.random.normal(KEY, (32, 16))
    idx = jax.random.randint(KEY, (4, 2), 0, 32)
    with pytest.raises(ValueError, match="bwd_impl"):
        bloom_embed_pallas(table, idx, interpret=True, bwd_impl="nope")
    logp = jax.nn.log_softmax(jax.random.normal(KEY, (2, 32)))
    H = jax.random.randint(KEY, (50, 2), 0, 32)
    with pytest.raises(ValueError, match="bwd_impl"):
        bloom_decode_pallas(logp, H, interpret=True, bwd_impl="nope")


def test_csr_bins_tiling_mismatch_is_rejected():
    """Bins carry (m, m_tile) as static metadata; the kernel entry must
    refuse bins built for a different tiling instead of silently
    scattering into the wrong output blocks."""
    from repro.kernels.bloom_csr import bin_csr, csr_scatter_add_pallas
    m, D, T, k = 96, 24, 6, 2
    idx = jax.random.randint(jax.random.fold_in(KEY, 4), (T, k), 0, m)
    g = jax.random.normal(KEY, (T, D))
    bins = bin_csr(idx, m, m_tile=16, e_tile=4)
    with pytest.raises(ValueError, match="mismatched bins"):
        csr_scatter_add_pallas(g, bins, m, m_tile=32, interpret=True)
    with pytest.raises(ValueError, match="mismatched bins"):
        csr_scatter_add_pallas(g, bins, m - 32, m_tile=16, interpret=True)


def test_interpret_defaults_to_backend_autodetect():
    """Satellite: no `interpret=` arg must NOT force interpret mode on TPU —
    kernels resolve it from the backend (True here: CPU test box)."""
    from repro.kernels.common import resolve_interpret
    assert resolve_interpret(None) == (jax.default_backend() != "tpu")
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    # entry points accept interpret=None end to end
    table = jax.random.normal(KEY, (32, 16))
    idx = jax.random.randint(KEY, (4, 2), 0, 32)
    out = bloom_embed_pallas(table, idx)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.bloom_embed_ref(table, idx)),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("bwd_impl", ["csr", "dense"])
def test_grad_through_model_pallas_vs_xla(bwd_impl):
    """jax.grad of the full LM loss: io_impl='pallas' == io_impl='xla'
    for both Bloom backwards (csr is the ModelConfig default)."""
    import dataclasses
    from repro import configs
    from repro.models import transformer as tf
    cfg_x = configs.get_smoke_config("qwen3-4b", dtype="float32")
    cfg_p = dataclasses.replace(cfg_x, io_impl="pallas",
                                bwd_impl=bwd_impl)
    params = tf.lm_init(KEY, cfg_x)
    toks = jax.random.randint(KEY, (2, 8), 0, cfg_x.vocab)

    def loss(p, cfg):
        l, _ = tf.lm_loss_fn(p, cfg, {"tokens": toks})
        return l

    gx = jax.grad(loss)(params, cfg_x)
    gp = jax.grad(loss)(params, cfg_p)
    flat_x = jax.tree.leaves(gx)
    flat_p = jax.tree.leaves(gp)
    for a, b in zip(flat_x, flat_p):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-3)


def test_pallas_io_impl_in_model():
    """A model configured with io_impl='pallas' must match io_impl='xla'."""
    from repro import configs
    from repro.models import transformer as tf
    cfg_x = configs.get_smoke_config("qwen3-4b", dtype="float32")
    import dataclasses
    cfg_p = dataclasses.replace(cfg_x, io_impl="pallas")
    params = tf.lm_init(KEY, cfg_x)
    toks = jax.random.randint(KEY, (2, 8), 0, cfg_x.vocab)
    lx, _ = tf.lm_loss_fn(params, cfg_x, {"tokens": toks})
    lp, _ = tf.lm_loss_fn(params, cfg_p, {"tokens": toks})
    assert float(lx) == pytest.approx(float(lp), rel=1e-5)


# ---------------------------------------------------------------------------
# Quantized tables (table_dtype, DESIGN.md §13)
# ---------------------------------------------------------------------------

from repro.core import quant  # noqa: E402  (quant tests below)


@pytest.mark.parametrize("table_dtype", list(quant.TABLE_DTYPES))
@pytest.mark.parametrize("T,k,m,D", [(7, 3, 64, 48), (32, 4, 128, 256)])
def test_bloom_embed_quantized_sweep(table_dtype, T, k, m, D):
    """Quantized forward == gather-sum over the DEQUANTIZED table (the
    XLA storage-model oracle): the kernel's in-VMEM dequant must match
    quantize+dequantize outside the kernel bit-for-bit in math."""
    table = jax.random.normal(KEY, (m, D), jnp.float32)
    idx = jax.random.randint(jax.random.fold_in(KEY, 1), (T, k), 0, m)
    got = bloom_embed_pallas(table, idx, d_tile=64, interpret=True,
                             table_dtype=table_dtype,
                             out_dtype=jnp.float32)
    q, s = quant.quantize_table(table, table_dtype)
    want = ref.bloom_embed_ref(quant.dequantize_table(q, s), idx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_bloom_embed_int8_close_to_fp32_oracle():
    """int8 storage stays within the ANALYTIC quantization bound of the
    float32 oracle: per-element error <= sum_j scales[idx[t, j]] / 2
    (per-row symmetric rounding contributes at most scale/2 per fetched
    row) — the module-doc bound of core.quant, end to end through the
    kernel."""
    T, k, m, D = 32, 4, 128, 256
    table = jax.random.normal(KEY, (m, D), jnp.float32)
    idx = jax.random.randint(jax.random.fold_in(KEY, 1), (T, k), 0, m)
    got = bloom_embed_pallas(table, idx, d_tile=64, interpret=True,
                             table_dtype="int8", out_dtype=jnp.float32)
    want = ref.bloom_embed_ref(table, idx)
    _, scales = quant.quantize_table(table, "int8")
    bound = jnp.take(scales, idx, axis=0).sum(-1, keepdims=True) / 2
    err = jnp.abs(got - want)
    assert float(jnp.max(err - bound)) <= 1e-5, (
        f"int8 embed error {float(err.max()):.4g} exceeds the analytic "
        f"scale/2-per-row bound ({float(bound.max()):.4g})")
    # and the bound itself is small on a unit-normal table (scales ~
    # amax/127 ~ 0.03): the storage knob costs < 1e-1 absolute here
    assert float(err.max()) < 0.1


@pytest.mark.parametrize("bwd_impl", ["dense", "csr"])
@pytest.mark.parametrize("table_dtype", ["int8", "fp8_e4m3"])
def test_bloom_embed_quantized_grad_straight_through(bwd_impl, table_dtype):
    """Gradients flow straight-through to the MASTER table: grad with a
    quantized forward == grad of the unquantized kernel (the fp32
    scatter-add backward is shared; only the forward's fetched rows
    change)."""
    T, k, m, D = 13, 3, 64, 32
    table = jax.random.normal(KEY, (m, D), jnp.float32)
    idx = jax.random.randint(jax.random.fold_in(KEY, 1), (T, k), 0, m)
    cot = jax.random.normal(jax.random.fold_in(KEY, 2), (T, D))

    def f(tbl, td):
        out = bloom_embed_pallas(tbl, idx, d_tile=32, interpret=True,
                                 bwd_impl=bwd_impl, table_dtype=td,
                                 out_dtype=jnp.float32)
        return jnp.vdot(out, cot)

    g_q = jax.grad(f)(table, table_dtype)
    g_f = jax.grad(f)(table, None)
    assert g_q.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(g_q), np.asarray(g_f),
                               rtol=1e-5, atol=1e-5)


def test_bloom_embed_fwd_quantized_matches_inline():
    """The frozen-params serve path (cached_quantized_table +
    bloom_embed_fwd_quantized) == the in-graph quantizing entry point."""
    from repro.core.bloom import cached_quantized_table
    from repro.kernels.bloom_embed import bloom_embed_fwd_quantized
    T, k, m, D = 9, 2, 64, 48
    spec = BloomSpec(d=300, m=m, k=k, seed=5)
    table = jax.random.normal(KEY, (m, D), jnp.float32)
    idx = jax.random.randint(jax.random.fold_in(KEY, 1), (T, k), 0, m)
    q, s = cached_quantized_table(spec, table, "int8")
    # identity-keyed cache: same table object must hit
    assert cached_quantized_table(spec, table, "int8")[0] is q
    got = bloom_embed_fwd_quantized(q, s, idx, d_tile=32, interpret=True)
    want = bloom_embed_pallas(table, idx, d_tile=32, interpret=True,
                              table_dtype="int8", out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("table_dtype", ["bfloat16", "int8", "fp8_e4m3"])
def test_bloom_decode_topk_quantized(table_dtype):
    """Fused decode-topk over quantized resident logp == decode-then-topk
    over the fake-quantized (dequantized) logp.  Quantization may permute
    ids on induced ties, so ids are scored through the oracle's matrix
    (the `picked` contract of the unquantized sweep)."""
    B, m, d, k, topk = 5, 64, 333, 3, 8
    logp = jax.nn.log_softmax(jax.random.normal(KEY, (B, m)))
    H = jax.random.randint(jax.random.fold_in(KEY, 2), (d, k), 0, m)
    vals, ids = bloom_decode_topk_pallas(logp, H, topk, b_tile=4,
                                         v_tile=64, interpret=True,
                                         table_dtype=table_dtype)
    q, s = quant.quantize_table(logp, table_dtype)
    scores = ref.bloom_decode_ref(quant.dequantize_table(q, s), H)
    want_v, _ = jax.lax.top_k(scores, topk)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(want_v),
                               rtol=1e-5, atol=1e-5)
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    np.testing.assert_allclose(np.asarray(picked), np.asarray(want_v),
                               rtol=1e-5, atol=1e-5)
    assert int(ids.min()) >= 0 and int(ids.max()) < d


def test_bloom_decode_topk_int8_close_to_fp32_oracle():
    """int8 resident logp stays within the analytic k * scale/2 bound of
    the fp32 decode-topk values (each Eq. 3 score sums k row reads, each
    off by at most scale/2 after per-row symmetric rounding)."""
    B, m, d, k, topk = 8, 128, 1024, 4, 16
    logp = jax.nn.log_softmax(jax.random.normal(KEY, (B, m)))
    H = jax.random.randint(jax.random.fold_in(KEY, 2), (d, k), 0, m)
    vals, _ = bloom_decode_topk_pallas(logp, H, topk, b_tile=4, v_tile=128,
                                       interpret=True, table_dtype="int8")
    want_v, _ = jax.lax.top_k(ref.bloom_decode_ref(logp, H), topk)
    _, scales = quant.quantize_table(logp, "int8")
    bound = k * scales[:, None] / 2
    err = jnp.abs(vals - want_v)
    assert float(jnp.max(err - bound)) <= 1e-5, (
        f"int8 decode-topk error {float(err.max()):.4g} exceeds the "
        f"analytic k*scale/2 bound ({float(bound.max()):.4g})")
    assert float(err.max()) < 0.25


def test_bloom_decode_topk_inkernel_hash_matches_H():
    """hash_spec=(d, k, seed) drops the H operand and re-derives indices
    in-kernel, bit-identical to core.hashing.double_hash — so both paths
    gather the same rows.  The summed SCORES may differ by float fusion
    (XLA fuses the two paths differently, ~1 ulp; ids then permute only
    on near-exact ties), so values are compared to tight float tolerance
    and ids through the score matrix (the `picked` contract)."""
    from repro.core.bloom import cached_hash_matrix
    B, m, d, k, topk = 5, 64, 333, 3, 8
    spec = BloomSpec(d=d, m=m, k=k, seed=7)
    logp = jax.nn.log_softmax(jax.random.normal(KEY, (B, m)))
    H = cached_hash_matrix(spec)
    for td in (None, "int8"):
        v_h, _ = bloom_decode_topk_pallas(logp, H, topk, b_tile=4,
                                          v_tile=64, interpret=True,
                                          table_dtype=td)
        v_k, i_k = bloom_decode_topk_pallas(logp, None, topk, b_tile=4,
                                            v_tile=64, interpret=True,
                                            table_dtype=td,
                                            hash_spec=(d, k, spec.seed))
        np.testing.assert_allclose(np.asarray(v_k), np.asarray(v_h),
                                   rtol=1e-6, atol=1e-6)
        if td is None:
            scores = ref.bloom_decode_ref(logp, H)
        else:
            q, s = quant.quantize_table(logp, "int8")
            scores = ref.bloom_decode_ref(quant.dequantize_table(q, s), H)
        picked = jnp.take_along_axis(scores, i_k, axis=-1)
        np.testing.assert_allclose(np.asarray(picked), np.asarray(v_k),
                                   rtol=1e-6, atol=1e-6)


def test_bloom_decode_topk_quantized_row_skipping():
    """table_dtype composes with the occupancy grid: live rows match the
    dense quantized grid, fully-dead blocks return (-inf, 0)."""
    B, m, d, k, topk, b_tile = 8, 64, 333, 3, 5, 2
    logp = jax.nn.log_softmax(jax.random.normal(KEY, (B, m)))
    H = jax.random.randint(jax.random.fold_in(KEY, 2), (d, k), 0, m)
    active = jnp.asarray([True, False, False, False, True, True,
                          False, False])
    v_d, i_d = bloom_decode_topk_pallas(logp, H, topk, b_tile=b_tile,
                                        v_tile=64, interpret=True,
                                        table_dtype="int8")
    v_s, i_s = bloom_decode_topk_pallas(logp, H, topk, b_tile=b_tile,
                                        v_tile=64, interpret=True,
                                        table_dtype="int8", active=active)
    blk_live = np.asarray(active).reshape(-1, b_tile).any(axis=1)
    row_live = np.repeat(blk_live, b_tile)
    np.testing.assert_array_equal(np.asarray(v_s)[row_live],
                                  np.asarray(v_d)[row_live])
    np.testing.assert_array_equal(np.asarray(i_s)[row_live],
                                  np.asarray(i_d)[row_live])
    assert np.all(np.asarray(v_s)[~row_live] == -np.inf)
    assert np.all(np.asarray(i_s)[~row_live] == 0)


def test_table_dtype_validation():
    """Typos fail fast with the full menu, at every layer that accepts
    the knob (quant core, kernel entry, config __post_init__)."""
    import dataclasses
    from repro import configs
    from repro.configs.retrieval import get_retrieval_config
    with pytest.raises(ValueError, match="table_dtype must be one of"):
        quant.resolve_table_dtype("int4")
    # aliases canonicalize; "auto" only with allow_auto
    assert quant.resolve_table_dtype("fp32") == "float32"
    assert quant.resolve_table_dtype("auto", allow_auto=True) == "auto"
    with pytest.raises(ValueError, match="table_dtype"):
        quant.resolve_table_dtype("auto")
    table = jax.random.normal(KEY, (32, 16))
    idx = jax.random.randint(KEY, (4, 2), 0, 32)
    with pytest.raises(ValueError, match="table_dtype"):
        bloom_embed_pallas(table, idx, interpret=True, table_dtype="int4")
    with pytest.raises(ValueError, match="table_dtype"):
        get_retrieval_config("smoke", table_dtype="f16")
    cfg = configs.get_smoke_config("qwen3-4b")
    cfg_bad = dataclasses.replace(cfg, table_dtype="f16")
    from repro.models import io as io_lib
    with pytest.raises(ValueError, match="table_dtype"):
        io_lib.resolved_table_dtype(cfg_bad)


@pytest.mark.parametrize("table_dtype", ["bfloat16", "int8"])
def test_model_quantized_pallas_matches_xla_fake_quant(table_dtype):
    """Model layer: io_impl='pallas' with a table_dtype == io_impl='xla'
    fake-quantizing the same rows — the two storage models must rank and
    activate through identical dequantized values."""
    import dataclasses
    from repro import configs
    from repro.models import io as io_lib, transformer as tf
    cfg_x = configs.get_smoke_config("qwen3-4b", dtype="float32")
    cfg_x = dataclasses.replace(cfg_x, table_dtype=table_dtype)
    cfg_p = dataclasses.replace(cfg_x, io_impl="pallas")
    params = tf.lm_init(KEY, cfg_x)
    toks = jax.random.randint(KEY, (2, 8), 0, cfg_x.vocab)
    ex = io_lib.embed_tokens(params["io"], cfg_x, toks)
    ep = io_lib.embed_tokens(params["io"], cfg_p, toks)
    np.testing.assert_allclose(np.asarray(ex), np.asarray(ep),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("T", [384, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kv_write_rows_sweep(T, dtype):
    """One (KV, hd) column per slot at (layer, b, :, :, pos[b]), the rest
    of both pools untouched; a slot at pos == T (retired) writes nothing.
    T = 384 is three 128-lane windows, T = 128 one."""
    from repro.kernels.kv_write import kv_write_rows_pallas
    L, B, KV, hd = 3, 5, 2, 16
    ks = jax.random.split(KEY, 4)
    k_pool = jax.random.normal(ks[0], (L, B, KV, hd, T), dtype)
    v_pool = jax.random.normal(ks[1], (L, B, KV, hd, T), dtype)
    k_new = jax.random.normal(ks[2], (B, KV, hd), jnp.float32)
    v_new = jax.random.normal(ks[3], (B, KV, hd), jnp.float32)
    pos = jnp.asarray([0, T - 1, 127 % T, T, 130 % T], jnp.int32)
    layer = 1
    got_k, got_v = jax.jit(lambda *a: kv_write_rows_pallas(
        *a, interpret=True))(k_pool, v_pool, k_new, v_new, layer, pos)
    for got, pool, new in ((got_k, k_pool, k_new), (got_v, v_pool, v_new)):
        want = np.array(pool)
        for b, p in enumerate(np.asarray(pos)):
            if p < T:
                want[layer, b, :, :, p] = np.asarray(new[b].astype(dtype))
        np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("n,want", [(1, 128), (40, 128), (128, 128),
                                    (129, 256), (1_100, 1_152)])
def test_kv_write_needs_whole_lane_windows(n, want):
    """A pool holds whole 128-lane windows (``pool_len``); the row write
    refuses any other length rather than move a whole row per slot."""
    from repro.kernels.kv_write import kv_write_rows_pallas, pool_len
    assert pool_len(n) == want
    if n % 128 == 0:
        return
    pool = jnp.zeros((1, 2, 1, 8, n), jnp.float32)
    new = jnp.zeros((2, 1, 8), jnp.float32)
    with pytest.raises(ValueError, match=f"allocate pool_len.*{want}"):
        kv_write_rows_pallas(pool, pool, new, new, 0,
                             jnp.zeros((2,), jnp.int32), interpret=True)
