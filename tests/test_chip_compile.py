"""Ahead-of-time compiles of the Bloom Pallas kernels for a described TPU
v5e (no chip attached), at the widths the main path runs.

Interpret mode accepts kernels the chip's compiler refuses (unaligned
DMA slices, lane gathers across registers, rank-1 blocks); these compiles
run Mosaic itself.  Widths: qwen1.5-0.5b (vocab d=151,936, Bloom m=30,208,
k=4, d_model 1024, T = 8 x 512 training tokens) and the web10m retrieval
catalog (d=10M, m=8,192, k=2).

The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and every xdist worker imports
this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bloom_ce, bloom_csr, bloom_decode, bloom_embed
from repro.kernels.bloom_decode_topk import bloom_decode_topk_pallas

D_VOCAB, M, K, D_MODEL = 151_936, 30_208, 4, 1024
T = 8 * 512
B, TOPK = 8, 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    print(compiled.memory_analysis())
    return compiled


LOGP = ((B, M), jnp.float32)
H = ((D_VOCAB, K), jnp.int32)
ACTIVE = ((B,), jnp.bool_)


@pytest.mark.parametrize("variant", ["dense", "row_skip", "int8_hash",
                                     "web10m"])
def test_decode_topk_compiles(one_chip, variant):
    kw = dict(interpret=False)
    if variant == "dense":
        _compile(lambda lp, h: bloom_decode_topk_pallas(lp, h, TOPK, **kw),
                 one_chip, LOGP, H)
    elif variant == "row_skip":
        _compile(lambda lp, h, a: bloom_decode_topk_pallas(
            lp, h, TOPK, active=a, **kw), one_chip, LOGP, H, ACTIVE)
    elif variant == "int8_hash":
        _compile(lambda lp, a: bloom_decode_topk_pallas(
            lp, None, TOPK, active=a, table_dtype="int8",
            hash_spec=(D_VOCAB, K, 0), **kw), one_chip, LOGP, ACTIVE)
    else:
        _compile(lambda lp, a: bloom_decode_topk_pallas(
            lp, None, 10, active=a, hash_spec=(10_000_000, 2, 0), **kw),
            one_chip, ((B, 8192), jnp.float32), ACTIVE)


def test_decode_scores_compiles(one_chip):
    _compile(lambda lp, h: bloom_decode.bloom_decode_pallas(
        lp, h, interpret=False), one_chip, LOGP, H)


@pytest.mark.parametrize("table,table_dtype", [
    (jnp.bfloat16, None), (jnp.float32, None), (jnp.float32, "int8")])
def test_embed_forward_compiles(one_chip, table, table_dtype):
    _compile(lambda t, i: bloom_embed.bloom_embed_pallas(
        t, i, interpret=False, table_dtype=table_dtype,
        out_dtype=jnp.bfloat16), one_chip, ((M, D_MODEL), table),
        ((T, K), jnp.int32))


def test_embed_backward_csr_compiles(one_chip):
    _compile(lambda g, i: bloom_csr.bloom_embed_bwd_csr_pallas(
        g, i, M, interpret=False), one_chip,
        ((T, D_MODEL), jnp.bfloat16), ((T, K), jnp.int32))


@pytest.mark.parametrize("kernel", ["embed", "decode"])
def test_dense_backward_compiles(one_chip, kernel):
    if kernel == "embed":
        _compile(lambda g, i: bloom_embed.bloom_embed_bwd_pallas(
            g, i, M, interpret=False), one_chip,
            ((T, D_MODEL), jnp.bfloat16), ((T, K), jnp.int32))
    else:
        _compile(lambda g, h: bloom_decode.bloom_decode_bwd_pallas(
            g, h, M, interpret=False), one_chip, ((B, D_VOCAB), jnp.float32),
            H)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_ce_compiles(one_chip, direction):
    loss = lambda z, h: bloom_ce.bloom_ce_pallas(z, h, interpret=False)
    fn = loss if direction == "fwd" else jax.grad(
        lambda z, h: loss(z, h).sum())
    _compile(fn, one_chip, ((T, M), jnp.float32), ((T, K), jnp.int32))
