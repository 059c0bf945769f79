"""Pallas TPU kernels: fused Bloom softmax cross-entropy (paper's training
loss in the compressed m-space), forward and backward.

Forward:   loss[t] = logsumexp(z[t, :]) - (1/k) * sum_{j<k} z[t, h[t, j]]
Backward:  dz[t, :] = g[t] * (softmax(z[t, :]) - onehot_count(h[t, :]) / k)

Fusing the logsumexp with the k-gather means the m-dim logits row is read
from HBM exactly once (the unfused path reads it three times: max, exp-sum,
gather).  The forward additionally emits the per-token ``lse`` as a VJP
residual, so the backward rebuilds softmax(z) = exp(z - lse) from ONE read
of the logits row instead of re-running the max/exp-sum reduction — the
(T, m) row is touched once in each direction (DESIGN.md §4).

  grid = (nT,)
  z    — block (Tt, m) at (t, 0)
  h    — block (Tt, k) at (t, 0)
  loss/lse — blocks (Tt, 1) at (t, 0);  bwd adds g (Tt, 1) in, dz (Tt, m)
         out.  The k-pick is a masked row sum per hash, not a lane gather
         (Mosaic gathers lanes only within one vector register).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (onehot_count, pad_axis, resolve_interpret,
                                  sublane_rows)


# --------------------------------------------------------------------------
# Forward (loss + lse residual)
# --------------------------------------------------------------------------

def _picked_sum(z, h):
    """(Tt, 1) sum_j z[t, h[t, j]] without a lane gather: one masked row
    sum per hash over an iota compare (exactly one lane matches, so each
    pick is copied, not rounded)."""
    iota = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
    total = None
    for j in range(h.shape[1]):
        pj = jnp.sum(jnp.where(iota == h[:, j:j + 1], z, 0.0), axis=-1,
                     keepdims=True)
        total = pj if total is None else total + pj
    return total


def _fwd_kernel(z_ref, h_ref, loss_ref, lse_ref):
    z = z_ref[...].astype(jnp.float32)             # (Tt, m)
    h = h_ref[...]                                 # (Tt, k)
    zmax = z.max(axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(z - zmax), axis=-1, keepdims=True)) + zmax
    loss_ref[...] = lse - _picked_sum(z, h) / h.shape[1]
    lse_ref[...] = lse


def _row_tile(t_tile, T, dtype):
    """Token tile: at least one native sublane tile of the logits dtype
    (8 rows f32, 16 bf16) unless the whole (padded) T is one block."""
    return min(max(t_tile, sublane_rows(dtype)), T)


def _ce_fwd(logits, h_idx, t_tile, interpret):
    T, m = logits.shape
    k = h_idx.shape[1]
    t_tile = _row_tile(t_tile, T, logits.dtype)
    logits = pad_axis(logits, 0, t_tile)
    h_idx = pad_axis(h_idx, 0, t_tile)
    Tp = logits.shape[0]

    loss, lse = pl.pallas_call(
        _fwd_kernel,
        grid=(Tp // t_tile,),
        in_specs=[
            pl.BlockSpec((t_tile, m), lambda t: (t, 0)),
            pl.BlockSpec((t_tile, k), lambda t: (t, 0)),
        ],
        out_specs=[
            pl.BlockSpec((t_tile, 1), lambda t: (t, 0)),
            pl.BlockSpec((t_tile, 1), lambda t: (t, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Tp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Tp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(logits, h_idx)
    return loss[:T, 0], lse[:T, 0]


# --------------------------------------------------------------------------
# Backward (dz from the lse residual)
# --------------------------------------------------------------------------

def _bwd_kernel(z_ref, h_ref, lse_ref, g_ref, dz_ref, *, k):
    z = z_ref[...].astype(jnp.float32)             # (Tt, m)
    p = jnp.exp(z - lse_ref[...])                  # softmax via residual
    w = onehot_count(h_ref[...], z.shape[1])       # (Tt, m)
    dz_ref[...] = g_ref[...] * (p - w / k)         # g, lse (Tt, 1)


@functools.partial(jax.jit, static_argnames=("t_tile", "interpret"))
def bloom_ce_bwd_pallas(g: jnp.ndarray, logits: jnp.ndarray,
                        h_idx: jnp.ndarray, lse: jnp.ndarray,
                        t_tile: int = 8,
                        interpret: bool | None = None) -> jnp.ndarray:
    """g (T,) cotangent; logits (T, m); h_idx (T, k); lse (T,) residual
    -> dlogits (T, m) float32, one pass over the m row."""
    interpret = resolve_interpret(interpret)
    T, m = logits.shape
    k = h_idx.shape[1]
    t_tile = _row_tile(t_tile, T, logits.dtype)
    logits = pad_axis(logits, 0, t_tile)
    h_idx = pad_axis(h_idx, 0, t_tile)
    lse = pad_axis(lse[:, None], 0, t_tile)
    g = pad_axis(g[:, None], 0, t_tile)         # 0-cotangent pad rows -> dz 0
    Tp = logits.shape[0]

    dz = pl.pallas_call(
        functools.partial(_bwd_kernel, k=k),
        grid=(Tp // t_tile,),
        in_specs=[
            pl.BlockSpec((t_tile, m), lambda t: (t, 0)),
            pl.BlockSpec((t_tile, k), lambda t: (t, 0)),
            pl.BlockSpec((t_tile, 1), lambda t: (t, 0)),
            pl.BlockSpec((t_tile, 1), lambda t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((t_tile, m), lambda t: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((Tp, m), jnp.float32),
        interpret=interpret,
    )(logits, h_idx, lse, g)
    return dz[:T]


# --------------------------------------------------------------------------
# custom_vjp glue + public entry point
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _bloom_ce(logits, h_idx, t_tile, interpret):
    loss, _ = _ce_fwd(logits, h_idx, t_tile, interpret)
    return loss


def _bloom_ce_vjp_fwd(logits, h_idx, t_tile, interpret):
    loss, lse = _ce_fwd(logits, h_idx, t_tile, interpret)
    return loss, (logits, h_idx, lse)


def _bloom_ce_vjp_bwd(t_tile, interpret, res, g):
    logits, h_idx, lse = res
    dz = bloom_ce_bwd_pallas(g, logits, h_idx, lse, t_tile=t_tile,
                             interpret=interpret)
    return dz.astype(logits.dtype), None


_bloom_ce.defvjp(_bloom_ce_vjp_fwd, _bloom_ce_vjp_bwd)


@functools.partial(jax.jit, static_argnames=("t_tile", "interpret"))
def bloom_ce_pallas(logits: jnp.ndarray, h_idx: jnp.ndarray,
                    t_tile: int = 8,
                    interpret: bool | None = None) -> jnp.ndarray:
    """logits (T, m); h_idx (T, k) int32 -> per-token loss (T,) float32.

    Differentiable: jax.grad w.r.t. `logits` runs the fused lse-residual
    backward kernel (one HBM read of the row, no re-softmax).
    """
    return _bloom_ce(logits, h_idx, min(t_tile, max(logits.shape[0], 1)),
                     resolve_interpret(interpret))
