"""Plain float32 reference of a Bloom-embedded decoder-only LM of the
Qwen1.5 kind (RMSNorm, rotary positions on split halves, biased QKV,
causal softmax attention, SwiGLU MLP, tied Bloom input/output table).

* Input: ``x = sum_j E[h_j(token)]`` (paper Eq. 1 as a k-row gather).
* Output: m-dim logits ``x @ E^T``; a vocabulary item scores
  ``sum_j logp[h_j(item)]`` (Eq. 3).

Weights come as a flat {path: float32 array} with the layers stacked on
the first axis.  Every matmul runs at ``precision="highest"``.  With
``quant=float8_e4m3fn`` every matmul input (weights and activations) is
first rounded to that type: the control that computes in the precision
below the configuration's bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference import bloom_hash


def _q(a, quant):
    return a if quant is None else a.astype(quant).astype(jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * freqs        # (S, hd/2)
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg, quant, x, lp):
    q8 = functools.partial(_q, quant=quant)
    S = x.shape[0]
    pos = jnp.arange(S)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = q8(_rms(x, lp["norm1/scale"], eps))
    q = jnp.einsum("sd,dhk->shk", h, q8(lp["attn/wq"])) + lp["attn/bq"]
    k = jnp.einsum("sd,dhk->shk", h, q8(lp["attn/wk"])) + lp["attn/bk"]
    v = jnp.einsum("sd,dhk->shk", h, q8(lp["attn/wv"])) + lp["attn/bv"]
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("shk,thk->hst", q8(q), q8(k)) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hst,thk->shk", q8(a), q8(v))
    x = x + jnp.einsum("shk,hkd->sd", q8(o), q8(lp["attn/wo"]))
    h = q8(_rms(x, lp["norm2/scale"], eps))
    g = h @ q8(lp["ffn/w_gate"])
    u = h @ q8(lp["ffn/w_up"])
    return x + q8(jax.nn.silu(g) * u) @ q8(lp["ffn/w_down"])


def _layer_params(params):
    pre = "blocks/sub0/"
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def forward(params, tokens, cfg: dict, quant=None):
    """tokens (S,) int -> (S, m) float32 logits."""
    m, k = cfg["bloom_m"], cfg["bloom_k"]
    E = _q(params["io/embed"], quant)
    with jax.default_matmul_precision("highest"):
        idx = bloom_hash.indices(tokens, k=k, m=m, seed=cfg["bloom_seed"])
        x = jnp.take(E, idx, axis=0).sum(axis=1)           # (S, D)
        body = functools.partial(_layer, cfg, quant)
        x, _ = jax.lax.scan(lambda c, lp: (body(c, lp), None), x,
                            _layer_params(params))
        x = _rms(x, params["final_norm/scale"], cfg["rms_norm_eps"])
        return _q(x, quant) @ E.T


def _item_scores(logp_t, ids, cfg):
    """logp_t (m, P) -> (n, P) Eq. 3 scores of items ``ids`` (n,)."""
    idx = bloom_hash.indices(ids, k=cfg["bloom_k"], m=cfg["bloom_m"],
                             seed=cfg["bloom_seed"])
    return jnp.take(logp_t, idx, axis=0).sum(axis=1)


def recovery_gaps(logp, logp_alt, tokens, cfg: dict, chunk: int = 16384):
    """Eq. 3 over the whole vocabulary at P positions.

    logp (P, m): the reference's log-probs.  Returns (gap_tokens,
    gap_alt), each (P,): how far below the reference's best item score
    lie the given ``tokens`` (P,), and the items that ``logp_alt``
    ranks first (None where ``logp_alt`` is None)."""
    V = cfg["vocab_size"]
    lt = logp.T
    at = None if logp_alt is None else logp_alt.T
    P = logp.shape[0]
    best = jnp.full((P,), -jnp.inf)
    alt_best = jnp.full((P,), -jnp.inf)
    alt_id = jnp.zeros((P,), jnp.int32)
    for start in range(0, V, chunk):
        ids = jnp.arange(start, min(start + chunk, V), dtype=jnp.int32)
        best = jnp.maximum(best, _item_scores(lt, ids, cfg).max(0))
        if at is not None:
            s = _item_scores(at, ids, cfg)
            i = jnp.argmax(s, axis=0)
            v = s[i, jnp.arange(P)]
            take = v > alt_best
            alt_best = jnp.where(take, v, alt_best)
            alt_id = jnp.where(take, ids[i], alt_id)

    def own(tok):
        idx = bloom_hash.indices(tok, k=cfg["bloom_k"], m=cfg["bloom_m"],
                                 seed=cfg["bloom_seed"])      # (P, k)
        return jnp.take_along_axis(logp, idx, axis=1).sum(1)

    gap = best - own(tokens)
    gap_alt = None if at is None else best - own(alt_id)
    return gap, gap_alt
