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
import re

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


# -- the qwen1.5-0.5b slot-pool decode step, whole ----------------------

POOL_SLOTS, POOL_LEN, POOL_TOPK = 32, 1_280, 8
_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1,
          "s8": 1, "u8": 1}
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]*)\]\S* "
                    r"([\w\-]+)\(([^)]*)\)")
_PARAM = re.compile(r"%([\w.\-]+): (\w+)\[([\d,]*)\]")


def _nbytes(dtype, dims):
    n = _BYTES.get(dtype, 4)
    for d in filter(None, dims.split(",")):
        n *= int(d)
    return n


def _moved(hlo_text):
    """(op, instruction, bytes) of every copy, select and
    dynamic-update-slice in the optimized HLO, fused or not: the bytes a
    copy or select writes, and the size of the update a
    dynamic-update-slice writes into its operand."""
    size = {m.group(1): _nbytes(m.group(2), m.group(3))
            for m in _PARAM.finditer(hlo_text)}
    found = []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, dtype, dims, op, operands = m.groups()
        size[name] = _nbytes(dtype, dims)
        if op in ("copy", "select"):
            found.append((op, name, size[name]))
        elif op == "dynamic-update-slice":
            update = operands.split(",")[1].strip().lstrip("%")
            found.append((op, name, size.get(update, size[name])))
    return found


def _pool_step(one_chip, monkeypatch, max_len):
    """The served qwen1.5-0.5b pool step (32 slots, bfloat16 KV, Eq. 3
    top-8) at ``max_len``, compiled for the described chip; returns it
    and the bytes of one layer's k cache at ``max_len``."""
    from repro import configs
    from repro.kernels import kv_write
    from repro.launch import steps as steps_lib
    from repro.models import transformer as tf
    # the backend here is the CPU: compile the row write as Mosaic
    monkeypatch.setattr(kv_write, "resolve_interpret", lambda i: False)
    cfg = configs.get_config("qwen1.5-0.5b")
    place = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,  # noqa: E731
                                           sharding=one_chip)
    params = jax.eval_shape(lambda: steps_lib.cast_params_for_compute(
        steps_lib.init_fn_for(cfg)(jax.random.PRNGKey(0)), cfg))
    pool = jax.eval_shape(lambda: tf.init_lm_cache(
        cfg, POOL_SLOTS, max_len, dtype=jnp.bfloat16))
    args = (jax.tree.map(place, params),
            place(jax.ShapeDtypeStruct((POOL_SLOTS, 1), jnp.int32)),
            jax.tree.map(place, pool),
            place(jax.ShapeDtypeStruct((POOL_SLOTS,), jnp.int32)),
            place(jax.ShapeDtypeStruct((POOL_SLOTS,), jnp.bool_)))
    compiled = jax.jit(steps_lib.make_slot_decode_step(cfg, topk=POOL_TOPK),
                       donate_argnums=(2,)).lower(*args).compile()
    layer_bytes = (POOL_SLOTS * max_len * cfg.num_kv_heads
                   * cfg.resolved_head_dim * 2)
    return compiled, layer_bytes


def test_pool_decode_step_moves_no_pool(one_chip, monkeypatch):
    """The served qwen1.5-0.5b pool step (32 slots x 1,280 positions,
    bfloat16 KV, Eq. 3 top-8) writes its KV rows in place: no copy,
    select or dynamic-update-slice moves a buffer the size of one
    layer's cache or more, and the step needs under 1 GB of scratch
    (a masked write over the scanned caches needed 4.87 GB)."""
    compiled, layer_bytes = _pool_step(one_chip, monkeypatch, POOL_LEN)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert [m for m in _moved(text) if m[2] >= layer_bytes] == []
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_pool_decode_step_compiles_at_an_odd_length(one_chip, monkeypatch):
    """A pool sized for 1,100 positions, no multiple of 128, is
    allocated at 1,152 (whole 128-lane windows), so the row write still
    moves one window per slot: the step compiles for the chip, moves no
    layer-sized buffer and keeps its scratch under 1 GB."""
    compiled, layer_bytes = _pool_step(one_chip, monkeypatch, 1_100)
    text = compiled.as_text()
    assert "bf16[24,32,16,64,1152]" in text
    assert [m for m in _moved(text) if m[2] >= layer_bytes] == []
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
