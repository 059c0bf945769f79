"""GQA attention: memory-efficient chunked (flash-style) training path,
cached decode path, cross-attention, and a naive oracle.

Adaptation notes (DESIGN.md §4): on TPU we never materialize the (S, T)
score matrix for long sequences — the chunked path scans kv-blocks with a
running (max, sum, acc) triple, giving O(S·chunk) live memory under remat.
`causal_skip=True` switches to a statically-unrolled q-chunk loop whose
kv extent grows triangularly, removing the ~2x masked-FLOP waste of the
rectangle+mask formulation (a §Perf hillclimb lever).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels.kv_write import kv_write_rows_pallas
from repro.models import layers

NEG_INF = -1e30


def attention_init(key, cfg: ModelConfig, cross: bool = False):
    D = cfg.d_model
    hd = cfg.resolved_head_dim
    H, KV = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": layers.truncated_normal_init(ks[0], (D, H * hd), 1.0)
        .reshape(D, H, hd),
        "wk": layers.truncated_normal_init(ks[1], (D, KV * hd), 1.0)
        .reshape(D, KV, hd),
        "wv": layers.truncated_normal_init(ks[2], (D, KV * hd), 1.0)
        .reshape(D, KV, hd),
        "wo": layers.truncated_normal_init(ks[3], (H * hd, D), 1.0)
        .reshape(H, hd, D),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, hd), jnp.float32)
        p["bk"] = jnp.zeros((KV, hd), jnp.float32)
        p["bv"] = jnp.zeros((KV, hd), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = layers.rms_norm_init(hd)
        p["k_norm"] = layers.rms_norm_init(hd)
    return p


def _project_qkv(params, cfg: ModelConfig, xq, xkv, q_pos, kv_pos,
                 rope: bool):
    dt = xq.dtype
    q = jnp.einsum("bsd,dhk->bshk", xq, params["wq"].astype(dt))
    k = jnp.einsum("btd,dhk->bthk", xkv, params["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bthk", xkv, params["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(dt)
        k = k + params["bk"].astype(dt)
        v = v + params["bv"].astype(dt)
    if cfg.qk_norm:
        q = layers.rms_norm(params["q_norm"], q, cfg.norm_eps)
        k = layers.rms_norm(params["k_norm"], k, cfg.norm_eps)
    if rope and cfg.use_rope:
        q = layers.apply_rope(q, q_pos, cfg.rope_theta)
        k = layers.apply_rope(k, kv_pos, cfg.rope_theta)
    return q, k, v


def _gqa_split(q, num_kv: int):
    """(B, S, H, hd) -> (B, S, KV, G, hd) with G = H // KV."""
    B, S, H, hd = q.shape
    return q.reshape(B, S, num_kv, H // num_kv, hd)


def _expand_heads(q, k, v, num_heads: int, kv_axis: int = 2):
    """GQA -> MHA layout that PRESERVES tensor-parallel head sharding.

    §Perf iteration (qwen3-4b train_4k): reshaping q (B,S,H,hd) ->
    (B,S,KV,G,hd) splits the sharded H dim into two dims (8,4) neither of
    which divides a 16-way model axis, so GSPMD replicated every attention
    inner tensor on all devices (measured: ~2x HLO FLOPs, dominant memory
    term).  Repeating k/v to the full H count keeps the flat, shardable H
    dim on every attention operand; the repeat itself is a cheap broadcast
    of the small kv tensors.

    Returns q (B,S,H,1,hd), k/v (B,T,H,hd) — or, for a decode cache
    (B,KV,hd,T) with ``kv_axis=1``, (B,H,hd,T).
    """
    B, S, H, hd = q.shape
    rep = num_heads // k.shape[kv_axis]
    if rep > 1:
        k = jnp.repeat(k, rep, axis=kv_axis)
        v = jnp.repeat(v, rep, axis=kv_axis)
    return q.reshape(B, S, H, 1, hd), k, v


def naive_attention(q, k, v, *, causal: bool, q_pos=None, kv_pos=None,
                    kv_valid=None):
    """Oracle: materializes full scores. q:(B,S,KV,G,hd), k/v:(B,T,KV,hd)."""
    hd = q.shape[-1]
    scores = jnp.einsum("bskgh,btkh->bskgt", q, k) / np.sqrt(hd)
    scores = scores.astype(jnp.float32)
    if causal:
        mask = q_pos[:, :, None, None, None] >= kv_pos[:, None, None, None, :]
        scores = jnp.where(mask, scores, NEG_INF)
    if kv_valid is not None:
        scores = jnp.where(kv_valid[:, None, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bskgt,btkh->bskgh", w, v)


def _chunk_accumulate(q, k_c, v_c, m, l, acc, mask_c,
                      bf16_scores: bool = False):
    """One flash-style accumulation step over a kv chunk.

    bf16_scores=True keeps the (S, Ck) score/probability chain in bf16
    (flash2-style: running max/sum/acc stats stay f32) — halves the
    dominant HBM traffic of score-bound cells (§Perf whisper prefill);
    validated to ~2e-2 vs the f32 oracle.
    """
    hd = q.shape[-1]
    s = jnp.einsum("bskgh,bckh->bskgc", q, k_c) / np.sqrt(hd)
    sdt = q.dtype if bf16_scores else jnp.float32
    neg = jnp.asarray(NEG_INF if sdt == jnp.float32 else -3e38, sdt)
    if mask_c is None:          # §Perf: non-causal unpadded fast path —
        s = s.astype(sdt)           # no (B,S,H,1,Ck) mask broadcast/select
    else:
        s = jnp.where(mask_c, s.astype(sdt), neg)
    m_new = jnp.maximum(m, s.max(-1).astype(jnp.float32))
    p = jnp.exp(s - m_new[..., None].astype(sdt))
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(-1).astype(jnp.float32)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bskgc,bckh->bskgh", p.astype(q.dtype), v_c).astype(jnp.float32)
    return m_new, l_new, acc_new


def _blockify(k, v, kv_pos, kv_valid, chunk_k):
    """Pad + reshape kv tensors into (n_chunks, B, Ck, ...) blocks.

    kv_valid may be None (= everything valid); padding forces it back."""
    B, T, KV, hd = k.shape
    Ck = min(chunk_k, T)
    n_c = -(-T // Ck)
    pad = n_c * Ck - T
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if kv_valid is None:
            kv_valid = jnp.ones(kv_pos.shape, bool)
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=2**30)
        kv_valid = jnp.pad(kv_valid, ((0, 0), (0, pad)))
    kc = k.reshape(B, n_c, Ck, KV, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_c, Ck, KV, hd).transpose(1, 0, 2, 3, 4)
    pc = kv_pos.reshape(B, n_c, Ck).transpose(1, 0, 2)
    valc = (kv_valid.reshape(B, n_c, Ck).transpose(1, 0, 2)
            if kv_valid is not None else None)
    return kc, vc, pc, valc, n_c, Ck, pad


def _mask_for(causal, q_pos, p_c, v_ok):
    if v_ok is None and not causal:
        return None
    ok = jnp.ones_like(p_c, bool) if v_ok is None else v_ok
    mask = ok[:, None, None, None, :]
    if causal:
        mask = mask & (q_pos[:, :, None, None, None]
                       >= p_c[:, None, None, None, :])
    return mask


def _flash_fwd_scan(q, kc, vc, pc, valc, q_pos, causal, unroll,
                    bf16_scores=False):
    B, S, KV, G, hd = q.shape
    m0 = jnp.full((B, S, KV, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros_like(m0)
    a0 = jnp.zeros((*m0.shape, hd), jnp.float32)

    def body(carry, blk):
        m, l, acc = carry
        k_c, v_c, p_c, v_ok = blk
        mask = _mask_for(causal, q_pos, p_c, v_ok)
        return _chunk_accumulate(q, k_c, v_c, m, l, acc, mask,
                                 bf16_scores), None

    blks = ((kc, vc, pc, valc) if valc is not None
            else (kc, vc, pc, None))
    if unroll:
        carry = (m0, l0, a0)
        for i in range(kc.shape[0]):
            carry, _ = body(carry, (kc[i], vc[i], pc[i],
                                    None if valc is None else valc[i]))
        m, l, acc = carry
    else:
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), blks)
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), jnp.inf)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _flash_attention(q, k, v, q_pos, kv_pos, kv_valid, causal, chunk_k,
                     unroll, bf16_scores=False):
    """Memory-efficient attention with a flash-style *backward*.

    Plain autodiff of the forward scan makes XLA store every chunk's
    attention probabilities ((S, Ck) per step, all steps live at once in
    the scan-reverse) — measured 17 GiB/device at 4k and O(70 GiB) at 32k
    prefill.  The custom VJP recomputes p per chunk from the saved
    (out, lse), so live memory is O(S*(hd + Ck)).
    """
    out, _ = _flash_attention_fwd(q, k, v, q_pos, kv_pos, kv_valid, causal,
                                  chunk_k, unroll, bf16_scores)
    return out


def _flash_attention_fwd(q, k, v, q_pos, kv_pos, kv_valid, causal, chunk_k,
                         unroll, bf16_scores=False):
    kc, vc, pc, valc, *_ = _blockify(k, v, kv_pos, kv_valid, chunk_k)
    out, lse = _flash_fwd_scan(q, kc, vc, pc, valc, q_pos, causal, unroll,
                               bf16_scores)
    return out, (q, k, v, q_pos, kv_pos, kv_valid, out, lse)


def _flash_attention_bwd(causal, chunk_k, unroll, bf16_scores, res, do):
    q, k, v, q_pos, kv_pos, kv_valid, out, lse = res
    B, T, KV, hd = k.shape
    kc, vc, pc, valc, n_c, Ck, pad = _blockify(k, v, kv_pos, kv_valid,
                                               chunk_k)
    scale = 1.0 / np.sqrt(hd)
    do32 = do.astype(jnp.float32)
    delta = (do32 * out.astype(jnp.float32)).sum(-1)      # (B,S,KV,G)
    dq0 = jnp.zeros(q.shape, jnp.float32)

    def body(dq, blk):
        k_c, v_c, p_c, v_ok = blk
        mask = _mask_for(causal, q_pos, p_c, v_ok)
        sdt = q.dtype if bf16_scores else jnp.float32
        neg = NEG_INF if sdt == jnp.float32 else -3e38
        s = jnp.einsum("bskgh,bckh->bskgc", q, k_c) * scale
        if mask is None:
            s = s.astype(sdt)
        else:
            s = jnp.where(mask, s.astype(sdt), jnp.asarray(neg, sdt))
        p = jnp.exp((s - lse[..., None].astype(sdt)).astype(jnp.float32))
        pb = p.astype(q.dtype)
        dv_c = jnp.einsum("bskgc,bskgh->bckh", pb, do)
        dp = jnp.einsum("bskgh,bckh->bskgc", do, v_c).astype(jnp.float32)
        ds = (p * (dp - delta[..., None]) * scale).astype(q.dtype)
        dq = dq + jnp.einsum("bskgc,bckh->bskgh", ds,
                             k_c).astype(jnp.float32)
        dk_c = jnp.einsum("bskgc,bskgh->bckh", ds, q)
        return dq, (dk_c, dv_c)

    if unroll:
        dq, dks, dvs = dq0, [], []
        for i in range(n_c):
            dq, (dk_c, dv_c) = body(dq, (kc[i], vc[i], pc[i],
                                         None if valc is None
                                         else valc[i]))
            dks.append(dk_c)
            dvs.append(dv_c)
        dkc, dvc = jnp.stack(dks), jnp.stack(dvs)
    else:
        dq, (dkc, dvc) = jax.lax.scan(body, dq0, (kc, vc, pc, valc))
    dk = dkc.transpose(1, 0, 2, 3, 4).reshape(B, n_c * Ck, KV, hd)
    dv = dvc.transpose(1, 0, 2, 3, 4).reshape(B, n_c * Ck, KV, hd)
    if pad:
        dk, dv = dk[:, :T], dv[:, :T]
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None, None, None)


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def chunked_attention(q, k, v, *, causal: bool, chunk_k: int,
                      q_pos, kv_pos, kv_valid=None, unroll: bool = False,
                      bf16_scores: bool = False):
    """Flash-style attention: kv-chunk streaming softmax forward + flash
    backward (custom VJP — see _flash_attention).

    q: (B, S, KV, G, hd); k, v: (B, T, KV, hd).  Never materializes (S, T).
    unroll=True replaces lax.scan with a static loop (dry-run analysis
    mode: XLA cost_analysis counts while bodies once).
    """
    return _flash_attention(q, k, v, q_pos, kv_pos, kv_valid, causal,
                            chunk_k, unroll, bf16_scores)


def chunked_attention_causal_skip(q, k, v, *, chunk_q: int, chunk_k: int,
                                  q_pos, kv_pos, kv_valid=None,
                                  unroll: bool = False):
    """Triangular chunked attention: static q-chunk loop, each q-chunk only
    scans kv up to its own end — saving the ~2x masked-FLOP waste.

    Requires q and kv to be position-aligned (self-attention, q_pos ==
    kv_pos), the standard train/prefill case.
    """
    B, S = q.shape[:2]
    Cq = min(chunk_q, S)
    n_q = -(-S // Cq)
    assert n_q * Cq == S, "causal_skip path requires S % chunk_q == 0"
    outs = []
    for i in range(n_q):
        sl = slice(i * Cq, (i + 1) * Cq)
        kv_end = (i + 1) * Cq
        outs.append(chunked_attention(
            q[:, sl], k[:, :kv_end], v[:, :kv_end], causal=True,
            chunk_k=chunk_k, q_pos=q_pos[:, sl], kv_pos=kv_pos[:, :kv_end],
            kv_valid=None if kv_valid is None else kv_valid[:, :kv_end],
            unroll=unroll))
    return jnp.concatenate(outs, axis=1)


def self_attention(params, cfg: ModelConfig, x, positions,
                   valid: Optional[jnp.ndarray] = None,
                   causal: bool = True):
    """Full-sequence self-attention (train / prefill)."""
    q, k, v = _project_qkv(params, cfg, x, x, positions, positions,
                           rope=True)
    qg, k, v = _expand_heads(q, k, v, cfg.num_heads)
    if cfg.attn_impl == "naive":
        o = naive_attention(qg, k, v, causal=causal, q_pos=positions,
                            kv_pos=positions, kv_valid=valid)
    elif causal and cfg.causal_skip:
        o = chunked_attention_causal_skip(
            qg, k, v, chunk_q=cfg.attn_chunk_q, chunk_k=cfg.attn_chunk_k,
            q_pos=positions, kv_pos=positions, kv_valid=valid,
            unroll=cfg.unroll_for_analysis)
    else:
        o = chunked_attention(qg, k, v, causal=causal,
                              chunk_k=cfg.attn_chunk_k, q_pos=positions,
                              kv_pos=positions, kv_valid=valid,
                              unroll=cfg.unroll_for_analysis,
                              bf16_scores=cfg.attn_bf16_scores)
    B, S = x.shape[:2]
    o = o.reshape(B, S, cfg.num_heads, cfg.resolved_head_dim)
    return jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(x.dtype))


def self_attention_with_cache(params, cfg: ModelConfig, x, positions,
                              valid: Optional[jnp.ndarray] = None,
                              cache_dtype=jnp.bfloat16):
    """Prefill: full causal self-attention that also emits the KV cache."""
    q, k, v = _project_qkv(params, cfg, x, x, positions, positions,
                           rope=True)
    kv_k, kv_v = k, v                   # cache stores the compact GQA kv
    qg, k, v = _expand_heads(q, k, v, cfg.num_heads)
    if cfg.causal_skip:
        o = chunked_attention_causal_skip(
            qg, k, v, chunk_q=cfg.attn_chunk_q, chunk_k=cfg.attn_chunk_k,
            q_pos=positions, kv_pos=positions, kv_valid=valid,
            unroll=cfg.unroll_for_analysis)
    else:
        o = chunked_attention(qg, k, v, causal=True,
                              chunk_k=cfg.attn_chunk_k, q_pos=positions,
                              kv_pos=positions, kv_valid=valid,
                              unroll=cfg.unroll_for_analysis,
                              bf16_scores=cfg.attn_bf16_scores)
    B, S = x.shape[:2]
    o = o.reshape(B, S, cfg.num_heads, cfg.resolved_head_dim)
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(x.dtype))
    return out, {"k": _cache_layout(kv_k).astype(cache_dtype),
                 "v": _cache_layout(kv_v).astype(cache_dtype)}


def cross_attention(params, cfg: ModelConfig, x, kv_x, q_positions,
                    kv_valid: Optional[jnp.ndarray] = None):
    """Encoder-decoder cross attention (whisper). No RoPE, no causality."""
    B, T = kv_x.shape[:2]
    kv_pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    q, k, v = _project_qkv(params, cfg, x, kv_x, q_positions, kv_pos,
                           rope=False)
    qg, k, v = _expand_heads(q, k, v, cfg.num_heads)
    o = chunked_attention(qg, k, v, causal=False, chunk_k=cfg.attn_chunk_k,
                          q_pos=q_positions, kv_pos=kv_pos,
                          kv_valid=kv_valid,
                          unroll=cfg.unroll_for_analysis,
                          bf16_scores=cfg.attn_bf16_scores)
    S = x.shape[1]
    o = o.reshape(B, S, cfg.num_heads, cfg.resolved_head_dim)
    return jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(x.dtype))


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  dtype=jnp.bfloat16):
    """Zeroed KV cache, head-major with positions last: (B, KV, hd, T).

    Positions lie on the lanes and a head's hd values on the sublanes, so
    the layout the decode attention reads, the one its row write touches
    and the device's default layout are one and the same (no relayout of
    the cache, and no lane padding of a head dim under 128)."""
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, cfg.num_kv_heads, hd, max_len), dtype),
        "v": jnp.zeros((batch, cfg.num_kv_heads, hd, max_len), dtype),
    }


def _cache_layout(a):
    """(B, T, KV, hd) -> the cache's (B, KV, hd, T)."""
    return a.transpose(0, 2, 3, 1)


def _attend_cache(q, k, v, kv_valid):
    """One query per row against a (B, KV, hd, T) cache: the arithmetic
    of ``naive_attention`` (scores in the compute dtype, then masked
    softmax in float32).  q: (B, KV, G, hd); kv_valid: (B, T).
    Returns (B, KV, G, hd)."""
    hd = q.shape[-1]
    scores = jnp.einsum("bkgh,bkht->bkgt", q, k) / np.sqrt(hd)
    scores = scores.astype(jnp.float32)
    scores = jnp.where(kv_valid[:, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgt,bkht->bkgh", w, v)


def decode_self_attention(params, cfg: ModelConfig, x, cache, pos,
                          dist=None, layer=None):
    """Single-token decode against a KV cache.

    x: (B, 1, D); cache: {"k","v"} (B, KV, hd, T); pos: position of the
    new token (cache entries < pos are valid) — either a scalar int32
    (static batch: all rows at the same offset) or a (B,) int32 vector
    (continuous-batching slot pool: every slot decodes at its own
    sequence offset inside ONE compiled step).

    With ``layer`` (an int32 index, possibly traced), ``cache`` is the
    whole layer-stacked cache (L, B, KV, hd, T) carried through the
    layer loop: each row's new (k, v) is written in place at (layer,
    row, :, :, pos) and the attention reads that layer where it lies.
    Without it, the cache is this layer's own (B, KV, hd, T) block.
    Returns (out (B, 1, D), new_cache).
    """
    B, _, D = x.shape
    T = cache["k"].shape[-1]
    pos = jnp.asarray(pos)
    per_slot = pos.ndim == 1
    posb = (pos[:, None] if per_slot
            else jnp.broadcast_to(pos[None, None], (B, 1)))
    q, k_new, v_new = _project_qkv(params, cfg, x, x, posb, posb, rope=True)
    seq_sharded = (dist is not None
                   and cfg.num_kv_heads % dist.n_model != 0
                   and cfg.num_heads % dist.n_model != 0)
    if seq_sharded:
        # cache is SEQUENCE-sharded over `model` (no shardable head dim,
        # e.g. whisper); q must not carry head sharding on the same axis
        # or GSPMD moves the multi-GB cache.  Replicating the
        # single-token q costs one small wq gather — §Perf finding.
        from jax.sharding import PartitionSpec as P
        bx = dist.batch_spec_axes(B)
        rep = lambda a: dist.constrain(  # noqa: E731
            a, P(bx, *([None] * (a.ndim - 1))))
        q, k_new, v_new = rep(q), rep(k_new), rep(v_new)
    new = {"k": k_new[:, 0], "v": v_new[:, 0]}          # (B, KV, hd)
    if layer is not None:
        # in place: one (KV, hd) column per row, at the row's own
        # position; the stack is carried through the layer loop and
        # donated through the step, so no other part of it moves
        k, v = kv_write_rows_pallas(cache["k"], cache["v"], new["k"],
                                    new["v"], layer,
                                    jnp.broadcast_to(pos, (B,)))
        cache = {"k": k, "v": v}
        k_all, v_all = k[layer], v[layer]
    elif seq_sharded or per_slot:
        # masked (iota == pos) write: fully elementwise.  Needed when the
        # cache is sequence-sharded (a positional dynamic write makes
        # GSPMD reshard the whole multi-GB cache) and when pos is a (B,)
        # slot vector outside the carried loop (a sharded pool, the
        # enc-dec decoder).  Writes the exact same values as the in-place
        # path, so every path decodes the same tokens.
        sel = jnp.arange(T)[None, None, None, :] == posb.reshape(B, 1, 1, 1)
        cache = {n: jnp.where(sel, new[n].astype(c.dtype)[..., None], c)
                 for n, c in cache.items()}
        k_all, v_all = cache["k"], cache["v"]
    else:
        # unsharded/batch-sharded cache: write exactly one position.
        cache = {n: jax.lax.dynamic_update_slice_in_dim(
            c, new[n].astype(c.dtype)[..., None], pos, axis=3)
            for n, c in cache.items()}
        k_all, v_all = cache["k"], cache["v"]
    kv_valid = jnp.arange(T)[None, :] <= posb
    # every path reads with the heads expanded, so a sharded H dim stays
    # whole on every operand (_expand_heads) and all paths share one
    # arithmetic; decode reads the whole cache once -> bandwidth-bound;
    # scores are (B, H, 1, T) — small — so XLA fuses mask+softmax.
    qg, k_all, v_all = _expand_heads(q, k_all.astype(x.dtype),
                                     v_all.astype(x.dtype), cfg.num_heads,
                                     kv_axis=1)
    o = _attend_cache(qg[:, 0], k_all, v_all, kv_valid)
    o = o.reshape(B, 1, cfg.num_heads, cfg.resolved_head_dim)
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(x.dtype))
    return out, cache
