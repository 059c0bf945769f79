"""Decoder-only LM assembly: dense / MoE / SSM / hybrid families.

Layers are grouped into *super-blocks* of `period` sub-layers (period = 1
for homogeneous stacks, 8 for jamba's 1-attn:7-mamba interleave) and the
super-block stack is traversed with lax.scan over stacked weights —
HLO size and compile time are O(1) in depth (MaxText-style), and the remat
policy wraps exactly one super-block.

Modes:
  train    — full sequence, no caches (loss handled by the caller).
  prefill  — full sequence, emits decode caches + all-position logits.
  decode   — one token against caches at position `pos`.  On one device
             the stacked caches are carried through the layer loop and
             updated in place (kernels/kv_write.py); under a `dist` they
             pass through the scan as per-layer inputs and outputs.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.kv_write import pool_len
from repro.models import attention, io, layers, mamba2, moe


# --------------------------------------------------------------------------
# Layer-role layout
# --------------------------------------------------------------------------

def period_of(cfg: ModelConfig) -> int:
    p = cfg.attn_layer_period if cfg.attn_layer_period > 0 else 1
    q = cfg.moe_layer_period if cfg.moe is not None else 1
    return math.lcm(p, q)


def sublayer_roles(cfg: ModelConfig):
    """[(mixer, ffn)] for one period. mixer: attn|mamba; ffn: dense|moe|none."""
    roles = []
    for j in range(period_of(cfg)):
        mixer = "attn" if cfg._layer_is_attention(j) else "mamba"
        if cfg._layer_is_moe(j):
            ffn = "moe"
        elif cfg.d_ff > 0:
            ffn = "dense"
        else:
            ffn = "none"
        roles.append((mixer, ffn))
    return roles


def num_superblocks(cfg: ModelConfig) -> int:
    p = period_of(cfg)
    assert cfg.num_layers % p == 0, (
        f"num_layers {cfg.num_layers} must divide into period {p}")
    return cfg.num_layers // p


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------

def _sublayer_init(key, cfg: ModelConfig, j: int):
    mixer, ffn = sublayer_roles(cfg)[j]
    ks = jax.random.split(key, 3)
    p: Dict[str, Any] = {"norm1": layers.rms_norm_init(cfg.d_model)}
    if mixer == "attn":
        p["attn"] = attention.attention_init(ks[0], cfg)
    else:
        p["mamba"] = mamba2.mamba_init(ks[0], cfg)
    if ffn != "none":
        p["norm2"] = layers.rms_norm_init(cfg.d_model)
        p["ffn"] = (moe.moe_init(ks[1], cfg) if ffn == "moe"
                    else layers.swiglu_init(ks[1], cfg.d_model, cfg.d_ff))
    return p


def superblock_init(key, cfg: ModelConfig):
    p = period_of(cfg)
    ks = jax.random.split(key, p)
    return {f"sub{j}": _sublayer_init(ks[j], cfg, j) for j in range(p)}


def lm_init(key, cfg: ModelConfig):
    k_io, k_blocks, k_front = jax.random.split(key, 3)
    n_super = num_superblocks(cfg)
    block_keys = jax.random.split(k_blocks, n_super)
    params = {
        "io": io.io_init(k_io, cfg),
        "blocks": jax.vmap(lambda k: superblock_init(k, cfg))(block_keys),
        "final_norm": layers.rms_norm_init(cfg.d_model),
    }
    if cfg.frontend != "none":
        params["frontend_proj"] = layers.dense_init(
            k_front, cfg.d_model, cfg.d_model, bias=False)
    return params


# --------------------------------------------------------------------------
# Apply
# --------------------------------------------------------------------------

def _sublayer_apply(p, cfg: ModelConfig, j: int, x, positions, mode,
                    cache, pos, dist, layer=None):
    """One sub-layer.  With ``layer`` (decode in place), ``cache`` holds
    the whole layer-stacked caches and the result is their update."""
    mixer, ffn = sublayer_roles(cfg)[j]
    aux = jnp.zeros((), jnp.float32)
    new_cache = {}
    h = layers.rms_norm(p["norm1"], x, cfg.norm_eps)
    if mixer == "attn":
        if mode == "train":
            y = attention.self_attention(p["attn"], cfg, h, positions)
        elif mode == "prefill":
            y, kv = attention.self_attention_with_cache(
                p["attn"], cfg, h, positions, cache_dtype=h.dtype)
            new_cache["attn"] = kv
        else:
            y, kv = attention.decode_self_attention(
                p["attn"], cfg, h, cache["attn"], pos, dist=dist,
                layer=layer)
            new_cache["attn"] = kv
    else:
        if mode == "train":
            y = mamba2.mamba_apply(p["mamba"], cfg, h)
        elif mode == "prefill":
            y, mc = mamba2.mamba_apply(p["mamba"], cfg, h,
                                       return_cache=True)
            new_cache["mamba"] = mc
        elif layer is None:
            y, mc = mamba2.mamba_decode_step(p["mamba"], cfg, h,
                                             cache["mamba"])
            new_cache["mamba"] = mc
        else:
            # recurrent state has no sequence axis: the layer's is
            # replaced whole
            y, mc = mamba2.mamba_decode_step(
                p["mamba"], cfg, h,
                jax.tree.map(lambda a: a[layer], cache["mamba"]))
            new_cache["mamba"] = jax.tree.map(
                lambda a, n: a.at[layer].set(n.astype(a.dtype)),
                cache["mamba"], mc)
    x = x + y
    if dist is not None:
        x = dist.constrain_tokens(x)
    if ffn != "none":
        h = layers.rms_norm(p["norm2"], x, cfg.norm_eps)
        if ffn == "moe":
            y, aux = moe.moe_apply(p["ffn"], h, cfg, dist)
        else:
            y = layers.swiglu(p["ffn"], h)
        x = x + y
        if dist is not None:
            x = dist.constrain_tokens(x)
    return x, new_cache, aux


def _superblock_apply(bp, cfg: ModelConfig, x, positions, mode, cache,
                      pos, dist, layer=None):
    auxes = jnp.zeros((), jnp.float32)
    new_caches = {}
    for j in range(period_of(cfg)):
        sub_c = cache.get(f"sub{j}") if cache is not None else None
        x, nc, aux = _sublayer_apply(bp[f"sub{j}"], cfg, j, x, positions,
                                     mode, sub_c, pos, dist, layer)
        if nc:
            new_caches[f"sub{j}"] = nc
        auxes = auxes + aux
    return x, new_caches, auxes


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    else:
        policy = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=policy)


def init_lm_cache(cfg: ModelConfig, batch: int, cache_len: int,
                  dtype=jnp.bfloat16):
    """Zeroed decode caches, stacked (n_super, ...) to match scanned
    blocks; attention k/v are (n_super, B, KV, hd, T).

    T is ``cache_len`` rounded up to whole 128-lane windows, the unit
    the in-place row write moves (``kernels.kv_write.pool_len``); the
    positions past ``cache_len`` are never valid, so never attended."""
    sb = {}
    for j, (mixer, _) in enumerate(sublayer_roles(cfg)):
        if mixer == "attn":
            sb[f"sub{j}"] = {"attn": attention.init_kv_cache(
                cfg, batch, pool_len(cache_len), dtype)}
        else:
            sb[f"sub{j}"] = {"mamba": mamba2.init_mamba_cache(
                cfg, batch, dtype)}
    n = num_superblocks(cfg)
    return jax.tree.map(lambda a: jnp.zeros((n, *a.shape), a.dtype), sb)


def _frontend_concat(params, cfg: ModelConfig, x_tokens, embeds):
    if embeds is None:
        return x_tokens
    pre = layers.dense(params["frontend_proj"],
                       embeds.astype(x_tokens.dtype))
    return jnp.concatenate([pre, x_tokens], axis=1)


def lm_apply(params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
             mode: str = "train", caches=None, pos=None, dist=None):
    """Run the LM.

    batch: {"tokens": (B, S)} plus optional {"embeds": (B, S_emb, D)} for
    vlm/audio stub frontends.  Returns a dict:
      train   -> {logits (B, S_tot, m_vocab), aux}
      prefill -> {logits, aux, caches}
      decode  -> {logits (B, 1, m_vocab), aux, caches}   (needs caches+pos)

    decode `pos` is a scalar (static batch) or a (B,) vector of per-slot
    sequence offsets (continuous-batching slot pool — one compiled step
    serves slots at different positions; SSM caches are offset-free so
    only the attention cache write/mask depends on it).
    """
    tokens = batch["tokens"]
    x = io.embed_tokens(params["io"], cfg, tokens)
    x = _frontend_concat(params, cfg, x, batch.get("embeds"))
    B, S_tot = x.shape[:2]
    if mode == "decode":
        assert caches is not None and pos is not None
        positions = None
    else:
        positions = jnp.broadcast_to(jnp.arange(S_tot)[None], (B, S_tot))
    if dist is not None:
        x = dist.constrain_tokens(x)

    block = _remat(
        lambda bp, x, c: _superblock_apply(bp, cfg, x, positions, mode, c,
                                           pos, dist),
        cfg) if mode == "train" else (
        lambda bp, x, c: _superblock_apply(bp, cfg, x, positions, mode, c,
                                           pos, dist))

    if mode == "decode" and dist is None:
        # the stacked caches ride in the carry, so each layer updates
        # them where they lie: no per-layer slice out of the stack, no
        # restack into a second pool, and the donated input aliases the
        # output
        def body(carry, inp):
            x, aux, c = carry
            bp, i = inp
            x, c, a = _superblock_apply(bp, cfg, x, positions, mode, c,
                                        pos, dist, layer=i)
            return (x, aux + a, c), None

        n = num_superblocks(cfg)
        if cfg.scan_layers:
            (x, aux, new_caches), _ = jax.lax.scan(
                body, (x, 0.0, caches), (params["blocks"], jnp.arange(n)))
        else:
            carry = (x, 0.0, caches)
            for i in range(n):
                carry, _ = body(carry, (jax.tree.map(
                    lambda a: a[i], params["blocks"]), i))
            x, aux, new_caches = carry
    elif cfg.scan_layers:
        def body(carry, inp):
            x, aux = carry
            bp, c = inp
            x, nc, a = block(bp, x, c)
            return (x, aux + a), nc

        xs = (params["blocks"], caches)
        (x, aux), new_caches = jax.lax.scan(body, (x, 0.0), xs)
    else:
        n = num_superblocks(cfg)
        aux = jnp.zeros((), jnp.float32)
        ncs = []
        for i in range(n):
            bp = jax.tree.map(lambda a: a[i], params["blocks"])
            c = (None if caches is None
                 else jax.tree.map(lambda a: a[i], caches))
            x, nc, a = block(bp, x, c)
            aux = aux + a
            ncs.append(nc)
        new_caches = (jax.tree.map(lambda *a: jnp.stack(a), *ncs)
                      if ncs and ncs[0] else None)

    x = layers.rms_norm(params["final_norm"], x, cfg.norm_eps)
    logits = io.lm_logits(params["io"], cfg, x)
    if dist is not None:
        logits = dist.constrain_logits(logits)
    out = {"logits": logits, "aux": aux}
    if mode in ("prefill", "decode"):
        out["caches"] = new_caches
    return out


def lm_loss_fn(params, cfg: ModelConfig, batch, dist=None):
    """Next-token CE (+ MoE aux). batch: tokens (B,S), optional embeds,
    optional loss_mask (B, S-1)."""
    out = lm_apply(params, cfg, batch, mode="train", dist=dist)
    logits = out["logits"]
    tokens = batch["tokens"]
    n_front = logits.shape[1] - tokens.shape[1]
    logits = logits[:, n_front:]                    # text region only
    shift_logits = logits[:, :-1]
    if dist is not None:
        shift_logits = dist.constrain_logits(shift_logits)
    shift_labels = tokens[:, 1:]
    valid = batch.get("loss_mask")
    loss_tok = io.lm_loss(params["io"], cfg, shift_logits, shift_labels,
                          valid)
    denom = (valid.sum() if valid is not None
             else jnp.asarray(loss_tok.size, jnp.float32))
    loss = loss_tok.sum() / jnp.maximum(denom, 1.0)
    aux_w = cfg.moe.aux_loss_weight if cfg.moe is not None else 0.0
    total = loss + aux_w * out["aux"] / max(num_superblocks(cfg), 1)
    return total, {"ce": loss, "aux": out["aux"]}


def lm_prefill(params, cfg: ModelConfig, batch, dist=None):
    return lm_apply(params, cfg, batch, mode="prefill", dist=dist)


def lm_decode_step(params, cfg: ModelConfig, token, caches, pos, dist=None,
                   topk: int = 0):
    """token: (B, 1) -> next-token logits; optional vocab recovery.

    With topk > 0 also returns the paper's Eq. 3 top-k recovery over the
    original vocab (the serving path measured in Fig. 3 right).
    """
    out = lm_apply(params, cfg, {"tokens": token}, mode="decode",
                   caches=caches, pos=pos, dist=dist)
    if topk:
        scores, ids = io.recover_topk(cfg, out["logits"][:, 0], topk=topk)
        out["topk_scores"], out["topk_ids"] = scores, ids
    return out
