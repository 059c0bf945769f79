"""Attention: flash fwd/bwd vs naive oracle, causal-skip, GQA variants,
decode-vs-prefill consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import attention as A

KEY = jax.random.PRNGKey(0)


def _qkv(B=2, S=12, KV=2, G=2, hd=8, T=None):
    T = T or S
    q = jax.random.normal(KEY, (B, S, KV, G, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, T, KV, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, T, KV, hd))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    post = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    return q, k, v, pos, post


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("chunk", [4, 5, 12, 64])
def test_flash_matches_naive_fwd(causal, chunk):
    q, k, v, pos, post = _qkv()
    o1 = A.chunked_attention(q, k, v, causal=causal, chunk_k=chunk,
                             q_pos=pos, kv_pos=post)
    o2 = A.naive_attention(q, k, v, causal=causal, q_pos=pos, kv_pos=post)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_grads_match_naive(causal):
    q, k, v, pos, post = _qkv()

    def loss_chunk(q, k, v):
        o = A.chunked_attention(q, k, v, causal=causal, chunk_k=5,
                                q_pos=pos, kv_pos=post)
        return (o ** 2).sum()

    def loss_naive(q, k, v):
        o = A.naive_attention(q, k, v, causal=causal, q_pos=pos,
                              kv_pos=post)
        return (o ** 2).sum()

    g1 = jax.grad(loss_chunk, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_causal_skip_matches_rectangle():
    q, k, v, pos, post = _qkv(S=16)
    o1 = A.chunked_attention_causal_skip(q, k, v, chunk_q=4, chunk_k=4,
                                         q_pos=pos, kv_pos=post)
    o2 = A.chunked_attention(q, k, v, causal=True, chunk_k=4, q_pos=pos,
                             kv_pos=post)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


def test_kv_valid_masking():
    q, k, v, pos, post = _qkv(S=6)
    valid = jnp.array([[True] * 4 + [False] * 2] * 2)
    o1 = A.chunked_attention(q, k, v, causal=False, chunk_k=3, q_pos=pos,
                             kv_pos=post, kv_valid=valid)
    o2 = A.naive_attention(q, k[:, :4], v[:, :4], causal=False)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


def _mk_cfg(**kw):
    base = dict(name="t", num_layers=1, d_model=32, num_heads=4,
                num_kv_heads=2, head_dim=8, d_ff=64, vocab=64,
                dtype="float32", attn_chunk_q=4, attn_chunk_k=4)
    base.update(kw)
    return ModelConfig(**base)


@pytest.mark.parametrize("kw", [
    {}, {"qk_norm": True}, {"qkv_bias": True},
    {"num_kv_heads": 4}, {"use_rope": False}, {"causal_skip": True},
])
def test_self_attention_variants_shapes_and_finite(kw):
    cfg = _mk_cfg(**kw)
    params = A.attention_init(KEY, cfg)
    x = jax.random.normal(KEY, (2, 8, 32))
    pos = jnp.broadcast_to(jnp.arange(8)[None], (2, 8))
    y = A.self_attention(params, cfg, x, pos)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()


def test_decode_matches_full_attention_last_position():
    """Prefill S-1 tokens, decode token S-1 -> must equal a full-length
    self-attention's last position output."""
    cfg = _mk_cfg()
    params = A.attention_init(KEY, cfg)
    S = 8
    x = jax.random.normal(KEY, (2, S, 32))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (2, S))
    full = A.self_attention(params, cfg, x, pos)

    out_pre, kv = A.self_attention_with_cache(
        params, cfg, x[:, :S - 1],
        jnp.broadcast_to(jnp.arange(S - 1)[None], (2, S - 1)),
        cache_dtype=jnp.float32)
    cache = A.init_kv_cache(cfg, 2, S, dtype=jnp.float32)
    cache = {
        "k": cache["k"].at[..., :S - 1].set(kv["k"]),
        "v": cache["v"].at[..., :S - 1].set(kv["v"]),
    }
    dec, _ = A.decode_self_attention(params, cfg, x[:, S - 1:],
                                     cache, S - 1)
    np.testing.assert_allclose(np.asarray(dec[:, 0]),
                               np.asarray(full[:, -1]), atol=2e-4)


def test_cross_attention_shape():
    cfg = _mk_cfg()
    params = A.attention_init(KEY, cfg)
    x = jax.random.normal(KEY, (2, 5, 32))
    enc = jax.random.normal(jax.random.fold_in(KEY, 3), (2, 9, 32))
    pos = jnp.broadcast_to(jnp.arange(5)[None], (2, 5))
    y = A.cross_attention(params, cfg, x, enc, pos)
    assert y.shape == (2, 5, 32)
