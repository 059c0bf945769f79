"""In-place write of one new (k, v) column per slot into a layer-stacked
KV-cache pool (the slot-pool decode step, DESIGN.md §7).

The pool is (L, B, KV, hd, T): positions on the lanes, a head's hd values
on the sublanes, which is the layout the decode attention reads.  Slot
b's new (KV, hd) column goes to (layer, b, :, :, pos[b]).  Mosaic moves
whole (sublane, 128-lane) tiles, so each grid step reads the lane window
of slot b that holds pos[b], replaces that one lane and writes the window
back; the pools are aliased to the outputs, so nothing else of them
moves.  T must be a whole number of such windows (``pool_len``, applied
where the pool is allocated).  A slot at pos >= T (a retired slot the
step still runs) writes nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret

LANES = 128


def pool_len(n: int) -> int:
    """The pool length that holds ``n`` positions: ``n`` rounded up to a
    whole number of 128-lane windows."""
    return -(-n // LANES) * LANES


def _kernel(layer_ref, pos_ref, k_new, v_new, k_in, v_in, k_out, v_out, *,
            T: int):
    del layer_ref
    p = pos_ref[pl.program_id(0)]
    W = k_in.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, k_in.shape, k_in.ndim - 1)
    sel = (lane == p % W) & (p < T)
    k_out[...] = jnp.where(sel, k_new[...], k_in[...])
    v_out[...] = jnp.where(sel, v_new[...], v_in[...])


def kv_write_rows_pallas(k_pool, v_pool, k_new, v_new, layer, pos, *,
                         interpret=None):
    """k_pool, v_pool: (L, B, KV, hd, T); k_new, v_new: (B, KV, hd);
    layer: int32 scalar (may be traced); pos: (B,) int32.  Returns the
    pools with row b's column written at (layer, b, :, :, pos[b]).
    T must be a multiple of 128 (``pool_len``)."""
    L, B, KV, hd, T = k_pool.shape
    if T % LANES:
        raise ValueError(f"pool length {T} is not a multiple of {LANES}; "
                         f"allocate pool_len({T}) = {pool_len(T)}")
    W, last = LANES, T // LANES - 1

    def window(b, layer, pos):
        return (layer[0], b, 0, 0, jnp.minimum(pos[b] // W, last))

    pool_spec = pl.BlockSpec((None, None, KV, hd, W), window)
    row_spec = pl.BlockSpec((None, KV, hd, 1),
                            lambda b, layer, pos: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(B,),
        in_specs=[row_spec, row_spec, pool_spec, pool_spec],
        out_specs=[pool_spec, pool_spec])
    return pl.pallas_call(
        functools.partial(_kernel, T=T), grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)),
        # operands: layer, pos, k_new, v_new, k_pool, v_pool
        input_output_aliases={4: 0, 5: 1},
        interpret=resolve_interpret(interpret),
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      jnp.asarray(pos, jnp.int32),
      k_new.astype(k_pool.dtype)[..., None],
      v_new.astype(v_pool.dtype)[..., None], k_pool, v_pool)
