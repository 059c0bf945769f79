"""CSR-binned scatter-add backward for the Bloom kernels (DESIGN.md §4).

Both Bloom backwards are the same op: a k-way scatter-add of cotangent
rows into an (m, ·) gradient table,

    out[r, :] = sum_{entries e : val[e] == r} g[row[e], :].

The dense formulation (bloom_embed_bwd_pallas / bloom_decode_bwd_pallas)
makes that race-free by brute force: a grid over EVERY (m_tile, ·) block
with the entry axis innermost, re-reading the full cotangent once per
m-tile sweep — `nM` reads of `g` where the op needs ~k.  At production
shapes that is the one place the bytes-first rule is still violated
(qwen3-4b embed.bwd models 4.25x the single-pass floor, decode.bwd 53x).

This module restores the stream-once shape by *sorting instead of
sweeping*:

  1. ``bin_csr`` — a jitted binning pass.  The flat hash indices are
     argsorted by owning m-tile (stable, so same-tile entries keep token
     order) and laid out into fixed-size entry tiles of ``e_tile`` slots,
     each tile owned by exactly ONE m-tile (segments are padded up to the
     tile boundary; every m-tile owns >= 1 tile so every output block
     gets zero-initialized).  All shapes are static: with E entries and
     nM m-tiles the layout has ``NT = E // e_tile + nM`` tiles, the worst
     case of per-segment padding.  Per tile the pass emits the source-row
     list (``tok``), the in-tile m values (``val``, -1 pad), the owning
     m-block (``tile_mb``, ascending), a first-tile-of-block flag
     (``tile_first``) and the live-entry count (``tile_len``).

  2. ``csr_scatter_add_pallas`` — the binned backward kernel.  Grid
     ``(nD, NT)`` with entry tiles innermost; ``tok``/``tile_*`` ride in
     as scalar prefetch.  Each step DMAs EXACTLY the segment's live
     cotangent rows from HBM into VMEM scratch (mirroring the forward's
     row-DMA layout: each row arrives in its tile-aligned row block and
     is picked out in VMEM; pad slots are gated off with ``pl.when``), builds
     the (e_tile, m_tile) one-hot of the in-tile m values and accumulates
     ``w.T @ rows`` on the MXU into the output block selected by the
     *data-dependent* index map ``tile_mb[ie]``.  Because tiles arrive
     sorted, each (m_tile, d_tile) block is revisited only by one
     consecutive run of grid steps — race-free like the dense sweep, but
     `g` is read ~k times total (once per entry) instead of nM times, and
     an empty m-tile is one pad tile that fetches nothing (pinned
     resident like the decode-topk row-skipping grid) and writes zeros.

``modeled_embed_bwd_csr_bytes`` / ``modeled_decode_bwd_csr_bytes`` are the
single bytes-model source for the ``*.bwd.csr`` rows in
benchmarks/bench_kernels.py.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (BWD_M_TILE, fetch_row_blocks,
                                  onehot_count, pad_axis, pick_row,
                                  resolve_interpret, sublane_rows)

# Default entry-tile size of the binned backward: one MXU-friendly
# contraction depth per grid step, and the unit segments are padded to.
CSR_E_TILE = 128


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("tok", "val", "tile_mb", "tile_first",
                                "tile_len"),
                   meta_fields=("m", "m_tile"))
@dataclasses.dataclass(frozen=True)
class CSRBins:
    """Static-shaped CSR layout of one entry set, produced by bin_csr.

    NT = E // e_tile + nM tiles of e_tile slots (E = number of entries).
    ``m``/``m_tile`` ride along as STATIC pytree metadata (the clamped
    values the bins were built for), so the kernel entry can enforce the
    bins-match-tiling contract instead of trusting the caller.
    """

    tok: jnp.ndarray         # (NT*e_tile,) i32 source row per slot (pad 0;
    #                          pad DMAs are gated off via tile_len)
    val: jnp.ndarray         # (NT*e_tile, 1) i32 global m index, -1 pad
    tile_mb: jnp.ndarray     # (NT,) i32 owning m-block per tile, ascending
    tile_first: jnp.ndarray  # (NT,) i32 1 iff first tile of its m-block
    tile_len: jnp.ndarray    # (NT,) i32 live entries in tile, in [0, e_tile]
    m: int                   # output rows the bins cover
    m_tile: int              # CLAMPED m-tile the entries were binned by

    @property
    def e_tile(self) -> int:
        return self.tok.shape[0] // self.tile_mb.shape[0]

    @property
    def n_tiles(self) -> int:
        return self.tile_mb.shape[0]


def csr_tile_counts(m: int, n_entries: int, m_tile: int = BWD_M_TILE,
                    e_tile: int = CSR_E_TILE):
    """(nM, NT, e_tile) static tile geometry shared by bin_csr, the kernel
    entry point and the bytes models."""
    m_tile = min(m_tile, m)
    e_tile = min(e_tile, max(n_entries, 1))
    nM = -(-m // m_tile)
    NT = n_entries // e_tile + nM
    return nM, NT, e_tile


@functools.partial(jax.jit, static_argnames=("m", "m_tile", "e_tile"))
def bin_csr(idx: jnp.ndarray, m: int, m_tile: int = BWD_M_TILE,
            e_tile: int = CSR_E_TILE) -> CSRBins:
    """Bin flat hash indices into the per-m-tile segment layout.

    idx (T, k) int32 in [0, m) — rows are source rows of the cotangent
    (tokens for embed.bwd, vocab ids for decode.bwd on the transposed
    cotangent).  Fully jitted and static-shaped, so for embed it fuses
    into the training step (per-batch), and for decode it is computed
    once per BloomSpec and cached (core.bloom.cached_decode_bins).
    """
    T, k = idx.shape
    E = T * k
    nM, NT, e_tile = csr_tile_counts(m, E, m_tile, e_tile)
    m_tile = min(m_tile, m)

    flat = idx.reshape(-1).astype(jnp.int32)
    src_row = jnp.arange(E, dtype=jnp.int32) // k
    blk = flat // m_tile                                   # owning m-block
    order = jnp.argsort(blk, stable=True)
    sval, stok, sblk = flat[order], src_row[order], blk[order]

    counts = jnp.zeros((nM,), jnp.int32).at[blk].add(1)
    tiles_per = jnp.maximum(1, -(-counts // e_tile))       # >= 1 per block
    tile_off = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(tiles_per)[:-1]])
    seg_start = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])

    # destination slot of sorted entry j: its block's first tile plus its
    # position within the segment
    pos = jnp.arange(E, dtype=jnp.int32) - seg_start[sblk]
    dst = tile_off[sblk] * e_tile + pos
    tok = jnp.zeros((NT * e_tile,), jnp.int32).at[dst].set(stok)
    val = jnp.full((NT * e_tile,), -1, jnp.int32).at[dst].set(sval)

    # per-tile metadata; tiles past the last used one degrade to no-op
    # revisits of the final block (tile_len 0, tile_first 0)
    tile_mb = jnp.cumsum(
        jnp.zeros((NT,), jnp.int32).at[tile_off[1:]].add(1))
    tile_first = jnp.zeros((NT,), jnp.int32).at[tile_off].set(1)
    local_tile = jnp.arange(NT, dtype=jnp.int32) - tile_off[tile_mb]
    tile_len = jnp.clip(counts[tile_mb] - local_tile * e_tile, 0, e_tile)
    return CSRBins(tok=tok, val=val.reshape(-1, 1),
                   tile_mb=tile_mb.astype(jnp.int32),
                   tile_first=tile_first, tile_len=tile_len,
                   m=m, m_tile=m_tile)


def _csr_kernel(tok_ref, tmb_ref, tfirst_ref, tlen_ref, val_ref, g_ref,
                out_ref, blk, rows_ref, sems, *, e_tile, d_tile, m_tile,
                rows):
    ie = pl.program_id(1)
    d0 = pl.program_id(0) * d_tile
    e0 = ie * e_tile
    n = tlen_ref[ie]

    # zero the output block exactly once, at the head of its tile run
    @pl.when(tfirst_ref[ie] == 1)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    # DMA exactly the live cotangent rows of this segment tile (pad slots
    # are skipped — an empty tile touches no HBM at all); each arrives in
    # its tile-aligned row block (kernels.common.fetch_row_blocks)
    offs, copies = fetch_row_blocks(
        g_ref, [tok_ref[e0 + s] for s in range(e_tile)], blk, sems, d0,
        d_tile, rows, gate=lambda s: s < n)
    for s, c in enumerate(copies):
        pl.when(s < n)(c.wait)

    @pl.when(n > 0)
    def _():
        for s in range(e_tile):
            rows_ref[s:s + 1, :] = pick_row(blk, s, offs[s])
        base = tmb_ref[ie] * m_tile
        valid = val_ref[...] >= 0                        # (e_tile, 1)
        w = onehot_count(val_ref[...], m_tile, base)     # (e_tile, m_tile)
        # pad slots carry stale scratch; select them to 0 so the matmul
        # can never multiply garbage (0 * NaN would poison the block)
        g_rows = jnp.where(valid, rows_ref[...], 0.0)    # (e_tile, d_tile)
        out_ref[...] += jnp.dot(w.T, g_rows,
                                preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("m", "m_tile", "d_tile", "interpret"))
def csr_scatter_add_pallas(g: jnp.ndarray, bins: CSRBins, m: int,
                           m_tile: int = BWD_M_TILE, d_tile: int = 512,
                           interpret: bool | None = None) -> jnp.ndarray:
    """g (T, D) cotangent rows + bins over (T, k) indices -> (m, D) f32.

    out[r, :] = sum over binned entries with val == r of g[tok, :].
    `bins` must come from bin_csr with the same (m, m_tile) — enforced
    against the bins' static metadata; e_tile is recovered from the
    bins' static shapes.
    """
    interpret = resolve_interpret(interpret)
    T, D = g.shape
    m_tile = min(m_tile, m)
    d_tile = min(d_tile, D)
    e_tile = bins.e_tile
    if (bins.m, bins.m_tile) != (m, m_tile):
        raise ValueError(
            f"bins were built for (m={bins.m}, m_tile={bins.m_tile}) but "
            f"the kernel was called with (m={m}, m_tile={m_tile}) — "
            "mismatched bins would scatter into the wrong output blocks")
    rows = sublane_rows(g.dtype)
    g = pad_axis(pad_axis(g, 1, d_tile), 0, rows)
    mp = m + ((-m) % m_tile)
    Dp = g.shape[1]
    NT = bins.n_tiles
    grid = (Dp // d_tile, NT)                     # entry tiles innermost

    out = pl.pallas_call(
        functools.partial(_csr_kernel, e_tile=e_tile, d_tile=d_tile,
                          m_tile=m_tile, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,                # tok, tile_mb/first/len
            grid=grid,
            in_specs=[
                pl.BlockSpec((e_tile, 1),
                             lambda id_, ie, tok, tmb, tf, tl: (ie, 0)),
                pl.BlockSpec(memory_space=pl.ANY),      # g stays in HBM
            ],
            out_specs=pl.BlockSpec(
                (m_tile, d_tile),
                # data-dependent: the output block this tile's segment
                # owns; sorted tiles revisit it in one consecutive run
                lambda id_, ie, tok, tmb, tf, tl: (tmb[ie], id_)),
            scratch_shapes=[
                pltpu.VMEM((e_tile, rows, d_tile), g.dtype),
                pltpu.VMEM((e_tile, d_tile), jnp.float32),
                pltpu.SemaphoreType.DMA((e_tile,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((mp, Dp), jnp.float32),
        interpret=interpret,
    )(bins.tok, bins.tile_mb, bins.tile_first, bins.tile_len, bins.val, g)
    return out[:m, :D]


# --------------------------------------------------------------------------
# Backward entry points (the bwd_impl="csr" paths of the custom VJPs)
# --------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("m", "m_tile", "e_tile", "d_tile",
                                    "interpret"))
def bloom_embed_bwd_csr_pallas(g: jnp.ndarray, idx: jnp.ndarray, m: int,
                               m_tile: int = BWD_M_TILE,
                               e_tile: int = CSR_E_TILE, d_tile: int = 512,
                               interpret: bool | None = None,
                               bins: CSRBins | None = None) -> jnp.ndarray:
    """g (T, D) cotangent; idx (T, k) -> dtable (m, D) f32 scatter-add.

    Drop-in for bloom_embed_bwd_pallas; the binning pass runs in-graph
    (per batch) unless precomputed `bins` are passed.
    """
    if bins is None:
        bins = bin_csr(idx, m, m_tile=m_tile, e_tile=e_tile)
    return csr_scatter_add_pallas(g, bins, m, m_tile=m_tile,
                                  d_tile=d_tile, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("m", "m_tile", "e_tile", "interpret"))
def bloom_decode_bwd_csr_pallas(g: jnp.ndarray, H: jnp.ndarray, m: int,
                                m_tile: int = BWD_M_TILE,
                                e_tile: int = CSR_E_TILE,
                                interpret: bool | None = None,
                                bins: CSRBins | None = None) -> jnp.ndarray:
    """g (B, d) cotangent; H (d, k) -> dlogp (B, m) f32 scatter-add.

    The decode backward IS the embed backward on the transposed
    cotangent: dlogp.T[c, b] = sum_{i,j : H[i,j] == c} g.T[i, b] — so it
    reuses csr_scatter_add_pallas on g.T with H's bins (fixed per
    BloomSpec, cached by core.bloom.cached_decode_bins) and transposes
    back.  The two (B·d + B·m)-sized XLA transposes are counted in the
    bytes model and are noise next to the nM-fold dense re-reads.
    """
    if bins is None:
        bins = bin_csr(H, m, m_tile=m_tile, e_tile=e_tile)
    B = g.shape[0]
    # batch columns become lanes: the row DMAs and output blocks need a
    # whole 128-lane width, so a small batch is padded up to one
    gT = pad_axis(g.T, 1, 128)
    out = csr_scatter_add_pallas(gT, bins, m, m_tile=m_tile,
                                 d_tile=min(512, gT.shape[1]),
                                 interpret=interpret)          # (m, Bp)
    return out[:, :B].T


# --------------------------------------------------------------------------
# Bytes models (single source for benchmarks/bench_kernels.py .csr rows)
# --------------------------------------------------------------------------

# Modeled HBM passes of the in-graph radix/merge sort in bin_csr: read +
# write of the key/payload streams over a small constant number of
# passes.  Deliberately generous — at E = T*k ~ 16k int32 entries the
# whole binning pass is < 1% of the row traffic it saves.
SORT_PASSES = 4


def _bin_bytes(E: int, nM: int, NT: int, e_tile: int) -> int:
    """Bytes of one bin_csr run: the sort over (E,) keys+payloads plus
    the scattered tile-layout writes and per-tile metadata."""
    sort = SORT_PASSES * 2 * E * 4
    layout = 2 * (NT * e_tile) * 4          # tok + val writes
    meta = 3 * NT * 4 + 3 * nM * 4          # tile_mb/first/len, counts etc.
    return sort + layout + meta


def modeled_embed_bwd_csr_bytes(T: int, k: int, D: int, m: int,
                                m_tile: int = BWD_M_TILE,
                                e_tile: int = CSR_E_TILE,
                                d_tile: int = 512,
                                include_binning: bool = True) -> int:
    """Analytic HBM bytes of the CSR embed backward at a production
    shape.  Per d-block sweep the kernel fetches exactly the E = T*k live
    cotangent rows (sum of tile_len; pad slots are DMA-gated), streams
    the (NT*e_tile, 1) val tiles, and writes each output block once; the
    per-batch binning pass is included by default."""
    E = T * k
    nM, NT, e_tile = csr_tile_counts(m, E, m_tile, e_tile)
    d_tile = min(d_tile, D)
    nD = -(-D // d_tile)
    rows = E * d_tile * 4 * nD              # ~= E * D * 4: g read ~k times
    vals = nD * NT * e_tile * 4             # val stream, re-read per sweep
    prefetch = (NT * e_tile + 3 * NT) * 4   # tok + tile metadata (SMEM)
    out = m * D * 4                         # dtable written exactly once
    total = rows + vals + prefetch + out
    if include_binning:
        total += _bin_bytes(E, nM, NT, e_tile)
    return int(total)


def modeled_decode_bwd_csr_bytes(B: int, d: int, k: int, m: int,
                                 m_tile: int = BWD_M_TILE,
                                 e_tile: int = CSR_E_TILE) -> int:
    """Analytic HBM bytes of the CSR decode backward.  The cotangent is
    transposed to (d, B) around the shared row-scatter kernel (read +
    write each way); bins over H are per-BloomSpec and cached, so the
    binning pass is NOT in the per-step model (cached_decode_bins)."""
    E = d * k
    nM, NT, e_tile = csr_tile_counts(m, E, m_tile, e_tile)
    d_tile = min(512, B)                    # as bloom_decode_bwd_csr_pallas
    nD = -(-B // d_tile)                    # 1 whenever B <= 512
    transpose_in = 2 * B * d * 4            # g -> gT
    rows = nD * E * d_tile * 4              # ~= E * B * 4: one row/entry
    vals = nD * NT * e_tile * 4             # val stream, re-read per sweep
    prefetch = (NT * e_tile + 3 * NT) * 4
    out = m * B * 4 + 2 * B * m * 4         # write + transpose back
    return int(transpose_in + rows + vals + prefetch + out)
