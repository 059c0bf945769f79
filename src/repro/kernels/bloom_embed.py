"""Pallas TPU kernels: Bloom k-way gather-sum embedding lookup, forward and
backward (differentiable via jax.custom_vjp).

Forward:   out[t, :] = sum_{j<k} table[idx[t, j], :]
Backward:  dtable[r, :] = sum_{t, j : idx[t, j] == r} g[t, :]   (scatter-add)

TPU mapping (DESIGN.md §4):

* Forward — token-blocked grid ``(nT, nD)``.  The table is passed ONCE in
  ``pl.ANY`` (it stays in HBM); the kernel issues ``t_tile * k`` async row
  DMAs per step into a VMEM scratch and reduces over k in-register.  Each
  DMA moves the tile-aligned row block that holds the hashed row (a DMA
  slice must cover whole sublane tiles); the row is picked out in VMEM.  This
  replaces the seed kernel's one-token-per-grid-step layout with
  ``[table] * k`` duplicated operands: operand count drops k+1 -> 2 and grid
  steps drop ``t_tile``x, while the scalar-prefetched index array still lets
  the DMA engine run ahead of compute (the TPU analogue of the paper's
  'pre-computed hash matrix in RAM' fast path).

* Backward — the k-way scatter-add.  A data-dependent-output scatter races
  under the Pallas output pipeline (and interpret mode's block write-back),
  so this module's DENSE backward is formulated race-free as a blocked
  one-hot contraction: grid ``(nM, nD, nT)`` with tokens innermost; each
  step builds the ``(t_tile, m_tile)`` one-hot count matrix
  w[t, i] = #{j : idx[t, j] == i} (kernels.common.onehot_count) IN VMEM
  ONLY and accumulates ``w.T @ g`` into the revisited ``(m_tile, d_tile)``
  output block on the MXU.  The dense ``(T, m)`` one-hot gradient of the
  XLA fallback never exists in HBM — but the m-tile sweep re-reads ``g``
  nM times.  ``bwd_impl="csr"`` (the training default) instead routes the
  VJP through the CSR-binned backward of kernels/bloom_csr.py, which
  sorts entries by m-tile and reads ``g`` ~k times total; the dense
  kernel remains the oracle-adjacent fallback.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import quant
from repro.kernels.common import (BWD_M_TILE, fetch_row_blocks,
                                  onehot_count, pad_axis, pick_row,
                                  resolve_bwd_impl, resolve_interpret,
                                  sublane_rows)


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------

def _fwd_kernel(idx_ref, *refs, t_tile, k, d_tile, rows, scaled):
    """Gather-sum of t_tile tokens x k hash rows for one d-block.

    A DMA may only move whole sublane tiles, so each hashed row arrives
    inside the ``rows``-row aligned block that holds it (8 rows f32, 16
    bf16, 32 int8) and is picked out of that block in VMEM.  int8 tables
    (``scaled``) dequantize with the fetched row's scale, which rides the
    scalar-prefetch path next to the indices (DESIGN.md §13)."""
    if scaled:
        s_ref, table_ref, out_ref, blk, acc, sems = refs
    else:
        s_ref = None
        table_ref, out_ref, blk, acc, sems = refs
    t0 = pl.program_id(0) * t_tile
    d0 = pl.program_id(1) * d_tile
    offs, copies = fetch_row_blocks(
        table_ref, [idx_ref[(t0 + tt) * k + j] for tt in range(t_tile)
                    for j in range(k)], blk, sems, d0, d_tile, rows)
    for c in copies:
        c.wait()
    for tt in range(t_tile):
        total = None
        for j in range(k):
            e = tt * k + j
            x = pick_row(blk, e, offs[e])
            if scaled:
                x = x * s_ref[(t0 + tt) * k + j]
            total = x if total is None else total + x
        acc[tt:tt + 1, :] = total
    out_ref[...] = acc[...].astype(out_ref.dtype)


def _embed_fwd(table, idx, t_tile, d_tile, interpret, scales=None,
               out_dtype=None):
    m, D = table.shape
    T, k = idx.shape
    out_dtype = table.dtype if out_dtype is None else jnp.dtype(out_dtype)
    t_tile = min(max(t_tile, sublane_rows(out_dtype)), T)
    d_tile = min(d_tile, D)
    rows = sublane_rows(table.dtype)
    table = pad_axis(pad_axis(table, 1, d_tile), 0, rows)
    idx = pad_axis(idx, 0, t_tile)             # pad rows gather row 0: sliced
    Tp, Dp = idx.shape[0], table.shape[1]
    grid = (Tp // t_tile, Dp // d_tile)
    kernel = functools.partial(_fwd_kernel, t_tile=t_tile, k=k,
                               d_tile=d_tile, rows=rows,
                               scaled=scales is not None)
    # flat (Tp*k,) indices: a 1-D SMEM operand is not lane-padded
    operands = [idx.reshape(-1)]
    if scales is not None:
        # Per-fetched-row scales, gathered OUTSIDE the kernel (a (T, k)
        # float32 gather of the (m,) vector — tiny next to the row DMAs)
        # so they prefetch alongside the indices.
        operands.append(jnp.take(scales.astype(jnp.float32), idx,
                                 axis=0).reshape(-1))
    n_prefetch = len(operands)
    out_index = lambda t, d, *prefetch: (t, d)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch,
            grid=grid,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((t_tile, d_tile), out_index),
            scratch_shapes=[
                pltpu.VMEM((t_tile * k, rows, d_tile), table.dtype),
                pltpu.VMEM((t_tile, d_tile), jnp.float32),
                pltpu.SemaphoreType.DMA((t_tile * k,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((Tp, Dp), out_dtype),
        interpret=interpret,
    )(*operands, table)
    return out[:T, :D]


def _default_out_dtype(table_dtype, table):
    """out dtype when the caller leaves it implicit: float storage keeps
    its own dtype (legacy behavior); sub-byte storage widens to f32."""
    if table_dtype is None:
        return table.dtype
    st = quant.storage_dtype(table_dtype)
    return st if st in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)) \
        else jnp.dtype(jnp.float32)


def _embed_fwd_quant(table, idx, t_tile, d_tile, interpret, table_dtype,
                     out_dtype):
    if table_dtype is None:
        return _embed_fwd(table, idx, t_tile, d_tile, interpret,
                          out_dtype=out_dtype)
    if out_dtype is None:
        out_dtype = _default_out_dtype(table_dtype, table)
    qtable, scales = quant.quantize_table(table, table_dtype)
    return _embed_fwd(qtable, idx, t_tile, d_tile, interpret, scales=scales,
                      out_dtype=out_dtype)


# --------------------------------------------------------------------------
# Backward (dtable)
# --------------------------------------------------------------------------

def _bwd_kernel(idx_ref, g_ref, out_ref, *, m_tile):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    base = pl.program_id(0) * m_tile
    w = onehot_count(idx_ref[...], m_tile, base)         # (t_tile, m_tile)
    g = g_ref[...].astype(jnp.float32)                   # (t_tile, d_tile)
    out_ref[...] += jnp.dot(w.T, g, preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("m", "m_tile", "d_tile", "t_tile",
                                    "interpret"))
def bloom_embed_bwd_pallas(g: jnp.ndarray, idx: jnp.ndarray, m: int,
                           m_tile: int = BWD_M_TILE, d_tile: int = 512,
                           t_tile: int = 128,
                           interpret: bool | None = None) -> jnp.ndarray:
    """g (T, D) cotangent; idx (T, k) -> dtable (m, D) float32 scatter-add."""
    interpret = resolve_interpret(interpret)
    T, D = g.shape
    k = idx.shape[1]
    m_tile = min(m_tile, m)
    d_tile = min(d_tile, D)
    t_tile = min(t_tile, T)
    g = pad_axis(pad_axis(g, 0, t_tile), 1, d_tile)
    idx = pad_axis(idx, 0, t_tile, value=-1)   # -1 never matches the iota
    mp = m + ((-m) % m_tile)
    Tp, Dp = g.shape
    grid = (mp // m_tile, Dp // d_tile, Tp // t_tile)

    out = pl.pallas_call(
        functools.partial(_bwd_kernel, m_tile=m_tile),
        grid=grid,
        in_specs=[
            pl.BlockSpec((t_tile, k), lambda im, id_, it: (it, 0)),
            pl.BlockSpec((t_tile, d_tile), lambda im, id_, it: (it, id_)),
        ],
        out_specs=pl.BlockSpec((m_tile, d_tile),
                               lambda im, id_, it: (im, id_)),
        out_shape=jax.ShapeDtypeStruct((mp, Dp), jnp.float32),
        interpret=interpret,
    )(idx, g)
    return out[:m, :D]


# --------------------------------------------------------------------------
# custom_vjp glue + public entry point
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(2, 3, 4, 5, 6, 7, 8, 9, 10))
def _bloom_embed(table, idx, t_tile, d_tile, interpret, bwd_impl,
                 m_tile, bwd_t_tile, e_tile, table_dtype, out_dtype):
    return _embed_fwd_quant(table, idx, t_tile, d_tile, interpret,
                            table_dtype, out_dtype)


def _bloom_embed_vjp_fwd(table, idx, t_tile, d_tile, interpret, bwd_impl,
                         m_tile, bwd_t_tile, e_tile, table_dtype, out_dtype):
    out = _embed_fwd_quant(table, idx, t_tile, d_tile, interpret,
                           table_dtype, out_dtype)
    # `table` rides along for shape/dtype only — it is a live param anyway.
    return out, (idx, table)


def _bloom_embed_vjp_bwd(t_tile, d_tile, interpret, bwd_impl, m_tile,
                         bwd_t_tile, e_tile, table_dtype, out_dtype, res, g):
    idx, table = res
    if bwd_impl == "csr":
        from repro.kernels.bloom_csr import bloom_embed_bwd_csr_pallas
        dtable = bloom_embed_bwd_csr_pallas(
            g, idx, table.shape[0], m_tile=m_tile, e_tile=e_tile,
            d_tile=d_tile, interpret=interpret)
    else:
        # every caller tiling knob is forwarded (bwd_t_tile defaults to
        # the dense backward's own token tile, NOT the forward t_tile:
        # the fwd default of 8 would shrink the bwd grid 16x)
        dtable = bloom_embed_bwd_pallas(
            g, idx, table.shape[0], m_tile=m_tile, d_tile=d_tile,
            t_tile=bwd_t_tile, interpret=interpret)
    # Quantized tables (table_dtype != None) train straight-through: the
    # forward ran on quantize(table) but the scatter-add above is the
    # exact gradient of the UNquantized linear map, accumulated in f32
    # against the master table — round() has zero gradient, so STE is the
    # standard estimator (DESIGN.md §13).  The CSR/dense kernels are
    # unchanged in math; only the forward's fetched-row dtype differs.
    return dtable.astype(table.dtype), None


_bloom_embed.defvjp(_bloom_embed_vjp_fwd, _bloom_embed_vjp_bwd)


@functools.partial(jax.jit,
                   static_argnames=("t_tile", "d_tile", "interpret",
                                    "out_dtype"))
def bloom_embed_fwd_quantized(qtable: jnp.ndarray,
                              scales: jnp.ndarray | None,
                              idx: jnp.ndarray,
                              t_tile: int = 8, d_tile: int = 512,
                              interpret: bool | None = None,
                              out_dtype=jnp.float32) -> jnp.ndarray:
    """Forward-only gather-sum on a PRE-quantized table.

    The serve-path sibling of bloom_embed_pallas: callers with frozen
    params pay the quantize cost once (core.bloom.cached_quantized_table)
    and pass ``(qtable, scales)`` straight to the kernel — no per-call
    quantize in the graph, no VJP.  ``scales=None`` for the scale-free
    dtypes (f32/bf16/fp8); (m,) float32 per-row scales for int8.
    """
    return _embed_fwd(qtable, idx, t_tile, d_tile,
                      resolve_interpret(interpret), scales=scales,
                      out_dtype=out_dtype)


@functools.partial(jax.jit,
                   static_argnames=("t_tile", "d_tile", "interpret",
                                    "bwd_impl", "m_tile", "bwd_t_tile",
                                    "e_tile", "table_dtype", "out_dtype"))
def bloom_embed_pallas(table: jnp.ndarray, idx: jnp.ndarray,
                       t_tile: int = 8, d_tile: int = 512,
                       interpret: bool | None = None,
                       bwd_impl: str = "dense",
                       m_tile: int = BWD_M_TILE,
                       bwd_t_tile: int = 128,
                       e_tile: int | None = None,
                       table_dtype: str | None = None,
                       out_dtype=None) -> jnp.ndarray:
    """table (m, D), idx (T, k) int32 -> (T, D) = k-way gather-sum.

    Differentiable: jax.grad w.r.t. `table` runs the scatter-add backward
    selected by ``bwd_impl`` (validated vs the XLA oracle in
    tests/test_kernels.py):

      "dense" — the blocked one-hot-contraction sweep over every m-tile
                (oracle-adjacent fallback; re-reads g once per m-tile);
      "csr"   — the CSR-binned backward (kernels.bloom_csr): a jitted
                per-batch binning pass + segment row-DMA kernel that
                reads g ~k times total.

    All backward tiling knobs are threaded through the custom VJP:
    ``m_tile`` (both impls), ``bwd_t_tile`` (dense token tile) and
    ``e_tile`` (csr entry tile; None = kernels.bloom_csr.CSR_E_TILE).

    ``table_dtype`` (DESIGN.md §13) selects the table's storage dtype on
    the HBM side of the row DMAs: None leaves the table untouched (legacy
    path, bit-identical to before the knob existed); "float32"/"bfloat16"
    cast; "int8" quantizes per-row symmetric in-graph and dequantizes on
    the VMEM tile; "fp8_e4m3" casts scale-free.  Gradients are
    straight-through against the master table.  ``out_dtype`` overrides
    the output dtype (default: the float storage dtype, or float32 for
    the sub-byte dtypes).
    """
    bwd_impl, e_tile = resolve_bwd_impl(bwd_impl, e_tile)
    table_dtype = quant.resolve_table_dtype(table_dtype)
    return _bloom_embed(table, idx, t_tile, d_tile,
                        resolve_interpret(interpret), bwd_impl, m_tile,
                        bwd_t_tile, e_tile, table_dtype, out_dtype)
