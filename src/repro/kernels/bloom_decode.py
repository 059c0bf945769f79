"""Pallas TPU kernels: Bloom vocabulary recovery (paper Eq. 3), forward and
backward.

Forward:   scores[b, i] = sum_{j<k} logp[b, H[i, j]]
Backward:  dlogp[b, c]  = sum_{i, j : H[i, j] == c} g[b, i]   (scatter-add)

TPU mapping: the m-dim log-prob row is small (m = d/5 of a 152k vocab is
~30k fp32 = 120 KB) and is kept WHOLE in VMEM per batch tile, so the
per-item k-gather runs at VMEM bandwidth while the vocab axis streams
through the grid.  This inverts the GPU formulation (random HBM access)
into sequential-HBM + random-VMEM — the memory-hierarchy adaptation of
DESIGN.md §4.

  grid = (nB, nV)
  logp — block (Bt, m) of the (nB, Bt, m) row blocks at (b, 0, 0)
         (revisited across the vocab axis; Pallas keeps it resident in
         VMEM between consecutive grid steps)
  H^T  — block (k, Vt) at (0, v)
  out  — block (Bt, Vt) at (b, 0, v)

The k-gather is the two-level lane gather shared with the fused top-k
kernel (bloom_decode_topk.gather_scores).

The DENSE backward inverts the stream: grid (nM, nV) with the vocab axis
innermost; each step builds the (v_tile, m_tile) one-hot count matrix
w[i, c] = #{j : H[i, j] == c} from k iota-compares in VMEM and accumulates
``g_tile @ w`` into the revisited (B, m_tile) output block on the MXU —
race-free, and no (B, d, k) or (d, m) one-hot ever reaches HBM, but the
m-tile sweep re-reads the (B, d) cotangent and H nM times.
``bwd_impl="csr"`` (the training default) routes the VJP through the
CSR-binned backward (kernels/bloom_csr.py) on the transposed cotangent
with per-spec cached bins of H — one read of g plus ~k row fetches; the
dense kernel remains the oracle-adjacent fallback.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import quant
from repro.kernels.bloom_decode_topk import (LANES, chunk_indices,
                                             gather_scores, load_resident)
from repro.kernels.common import (BWD_M_TILE, onehot_count, pad_axis,
                                  resolve_bwd_impl, resolve_interpret)


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------

def _fwd_kernel(*refs, k, has_scales):
    logp_ref = refs[0]
    s_ref = refs[1] if has_scales else None
    h_ref, out_ref, lp_ref = refs[1 + has_scales:]

    # int8 logp (DESIGN.md §13) dequantizes once per row block, on the
    # resident f32 scratch the gather reads
    @pl.when(pl.program_id(1) == 0)
    def _():
        load_resident(logp_ref, s_ref, lp_ref)

    def chunk(c, carry):
        off = pl.multiple_of(c * LANES, LANES)
        out_ref[:, pl.ds(off, LANES)] = gather_scores(
            lp_ref, chunk_indices(h_ref, None, c, None, k))
        return carry

    jax.lax.fori_loop(0, out_ref.shape[1] // LANES, chunk, 0)


def _decode_fwd(logp, H, b_tile, v_tile, interpret, scales=None):
    B, m = logp.shape
    d, k = H.shape
    v_tile += (-v_tile) % LANES
    logp = pad_axis(pad_axis(logp, 0, b_tile), 1, LANES)
    Bp, mp = logp.shape
    nB = Bp // b_tile
    HT = pad_axis(H, 0, v_tile).T              # (k, dp): ids on lanes
    dp = HT.shape[1]

    in_specs = [pl.BlockSpec((None, b_tile, mp), lambda b, v: (b, 0, 0))]
    operands = [logp.reshape(nB, b_tile, mp)]
    if scales is not None:
        sg = pad_axis(scales.astype(jnp.float32), 0, b_tile)
        in_specs.append(pl.BlockSpec((None, b_tile, 1),
                                     lambda b, v: (b, 0, 0)))
        operands.append(sg.reshape(nB, b_tile, 1))
    in_specs.append(pl.BlockSpec((k, v_tile), lambda b, v: (0, v)))
    operands.append(HT)

    out = pl.pallas_call(
        functools.partial(_fwd_kernel, k=k, has_scales=scales is not None),
        grid=(nB, dp // v_tile),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, b_tile, v_tile),
                               lambda b, v: (b, 0, v)),
        out_shape=jax.ShapeDtypeStruct((nB, b_tile, dp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((b_tile, mp), jnp.float32)],
        interpret=interpret,
    )(*operands)
    return out.reshape(Bp, dp)[:B, :d]


def _decode_fwd_quant(logp, H, b_tile, v_tile, interpret, table_dtype):
    if table_dtype is None:
        return _decode_fwd(logp, H, b_tile, v_tile, interpret)
    qlogp, scales = quant.quantize_table(logp, table_dtype)
    return _decode_fwd(qlogp, H, b_tile, v_tile, interpret, scales=scales)


# --------------------------------------------------------------------------
# Backward (dlogp)
# --------------------------------------------------------------------------

def _bwd_kernel(h_ref, g_ref, out_ref, *, m_tile):
    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    base = pl.program_id(0) * m_tile
    w = onehot_count(h_ref[...], m_tile, base)           # (v_tile, m_tile)
    g = g_ref[...].astype(jnp.float32)                   # (B, v_tile)
    out_ref[...] += jnp.dot(g, w, preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("m", "m_tile", "v_tile", "interpret"))
def bloom_decode_bwd_pallas(g: jnp.ndarray, H: jnp.ndarray, m: int,
                            m_tile: int = BWD_M_TILE, v_tile: int = 2048,
                            interpret: bool | None = None) -> jnp.ndarray:
    """g (B, d) cotangent; H (d, k) -> dlogp (B, m) float32 scatter-add."""
    interpret = resolve_interpret(interpret)
    B, d = g.shape
    k = H.shape[1]
    m_tile = min(m_tile, m)
    v_tile = min(v_tile, d)
    g = pad_axis(g, 1, v_tile)
    H = pad_axis(H, 0, v_tile, value=-1)       # -1 never matches the iota
    mp = m + ((-m) % m_tile)
    dp = H.shape[0]
    grid = (mp // m_tile, dp // v_tile)

    out = pl.pallas_call(
        functools.partial(_bwd_kernel, m_tile=m_tile),
        grid=grid,
        in_specs=[
            pl.BlockSpec((v_tile, k), lambda im, iv: (iv, 0)),
            pl.BlockSpec((B, v_tile), lambda im, iv: (0, iv)),
        ],
        out_specs=pl.BlockSpec((B, m_tile), lambda im, iv: (0, im)),
        out_shape=jax.ShapeDtypeStruct((B, mp), jnp.float32),
        interpret=interpret,
    )(H, g)
    return out[:, :m]


# --------------------------------------------------------------------------
# custom_vjp glue + public entry point
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7, 8, 9))
def _bloom_decode(logp, H, bins_fn, b_tile, v_tile, interpret, bwd_impl,
                  m_tile, e_tile, table_dtype):
    return _decode_fwd_quant(logp, H, b_tile, v_tile, interpret, table_dtype)


def _bloom_decode_vjp_fwd(logp, H, bins_fn, b_tile, v_tile, interpret,
                          bwd_impl, m_tile, e_tile, table_dtype):
    return (_decode_fwd_quant(logp, H, b_tile, v_tile, interpret,
                              table_dtype), (logp, H))


def _bloom_decode_vjp_bwd(bins_fn, b_tile, v_tile, interpret, bwd_impl,
                          m_tile, e_tile, table_dtype, res, g):
    logp, H = res
    if bwd_impl == "csr":
        from repro.kernels.bloom_csr import bloom_decode_bwd_csr_pallas
        # bins_fn resolves HERE, at backward-trace time — forward-only
        # callers never pay the binning sort (the cached device arrays
        # are picked up as constants, like cached_hash_matrix elsewhere)
        bins = bins_fn() if bins_fn is not None else None
        dlogp = bloom_decode_bwd_csr_pallas(
            g, H, logp.shape[1], m_tile=m_tile, e_tile=e_tile,
            interpret=interpret, bins=bins)
    else:
        # all tiling knobs forwarded (m_tile was previously dropped)
        dlogp = bloom_decode_bwd_pallas(g, H, logp.shape[1],
                                        m_tile=m_tile, v_tile=v_tile,
                                        interpret=interpret)
    # table_dtype != None trains straight-through: the scatter-add is the
    # exact gradient of the unquantized linear map (the backward kernels
    # never read logp, so their math is untouched — DESIGN.md §13).
    return dlogp.astype(logp.dtype), None


_bloom_decode.defvjp(_bloom_decode_vjp_fwd, _bloom_decode_vjp_bwd)


@functools.partial(jax.jit,
                   static_argnames=("b_tile", "v_tile", "interpret",
                                    "bwd_impl", "m_tile", "e_tile",
                                    "bins_fn", "table_dtype"))
def bloom_decode_pallas(logp: jnp.ndarray, H: jnp.ndarray,
                        b_tile: int = 8, v_tile: int = 2048,
                        interpret: bool | None = None,
                        bwd_impl: str = "dense",
                        m_tile: int = BWD_M_TILE,
                        e_tile: int | None = None,
                        bins_fn=None,
                        table_dtype: str | None = None) -> jnp.ndarray:
    """logp (B, m) float; H (d, k) int32 -> scores (B, d) float32.

    Differentiable: jax.grad w.r.t. `logp` runs the scatter-add backward
    selected by ``bwd_impl`` — "dense" (the blocked m-tile sweep,
    oracle-adjacent fallback) or "csr" (the CSR-binned backward of
    kernels.bloom_csr, which reads the (B, d) cotangent once instead of
    once per m-tile).  ``bins_fn`` is an optional HASHABLE zero-arg
    callable returning precomputed bin_csr output for H; it is invoked
    only when the backward is traced, so forward-only calls never pay
    the binning pass (kernels.ops wires the per-spec
    core.bloom.cached_decode_bins thunk here — H is fixed per BloomSpec,
    so the sort amortizes to zero).  None on the csr path re-bins
    in-graph inside the backward.  All backward tiling knobs
    (``m_tile``, ``e_tile``) are threaded through the custom VJP.

    ``table_dtype`` (DESIGN.md §13) stores the resident (B, m) log-prob
    block in a narrower dtype: "int8" quantizes per-batch-row symmetric
    and dequantizes once per output tile in VMEM; "bfloat16"/"fp8_e4m3"
    cast (the kernel's astype(f32) is the dequant); None is the legacy
    exact path.  Gradients are straight-through against the f32 logp.
    """
    bwd_impl, e_tile = resolve_bwd_impl(bwd_impl, e_tile)
    b_tile = min(b_tile, logp.shape[0])
    v_tile = min(v_tile, H.shape[0])
    return _bloom_decode(logp, H, bins_fn, b_tile, v_tile,
                         resolve_interpret(interpret), bwd_impl, m_tile,
                         e_tile, quant.resolve_table_dtype(table_dtype))
