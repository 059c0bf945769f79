"""Shared plumbing for the Bloom Pallas kernel suite (DESIGN.md §4).

Every public ``*_pallas`` entry point takes ``interpret=None`` and resolves
it here: interpret mode off-TPU (CPU CI, tests, this box), compiled Mosaic
on TPU.  Passing an explicit bool still forces either mode — tests pin
``interpret=True`` so sweeps stay deterministic regardless of backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Default m-tile of the blocked backward kernels (bloom_embed_bwd_pallas,
# bloom_decode_bwd_pallas).  benchmarks/bench_kernels.py imports this to
# keep the committed *.bwd bytes models in lock-step with the kernels.
BWD_M_TILE = 512


def resolve_interpret(interpret: bool | None) -> bool:
    """None -> auto (interpret everywhere except real TPU)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def sublane_rows(dtype) -> int:
    """Rows of one native TPU tile of `dtype`: 8 for 32-bit, 16 for 16-bit,
    32 for 8-bit elements.  A block or DMA slice along the second-minor
    axis must cover whole tiles."""
    return 32 // jnp.dtype(dtype).itemsize


def fetch_row_blocks(src_ref, row_ids, blk, sems, d0, d_tile, rows,
                     gate=None):
    """Start one DMA per id in ``row_ids`` (scalars) of the ``rows``-row
    aligned block of HBM ``src_ref`` that holds that row, columns
    ``[d0, d0 + d_tile)``, into ``blk[e]`` of a (n, rows, d_tile) VMEM
    scratch.  Mosaic moves whole sublane tiles only, so a single-row DMA
    is refused; ``rows`` is sublane_rows(src dtype) and src's row count a
    multiple of it.  ``gate(e)`` (traced bool) skips an entry's DMA.
    Returns (in-block row offsets, copies); wait on the copies (under the
    same gate) before reading ``blk``."""
    offs, copies = [], []
    for e, r in enumerate(row_ids):
        start = pl.multiple_of((r // rows) * rows, rows)
        c = pltpu.make_async_copy(
            src_ref.at[pl.ds(start, rows), pl.ds(d0, d_tile)],
            blk.at[e], sems.at[e])
        if gate is None:
            c.start()
        else:
            pl.when(gate(e))(c.start)
        offs.append(r - start)
        copies.append(c)
    return offs, copies


def pick_row(blk, e, off):
    """(1, d_tile) float32 row ``off`` of the fetched block ``blk[e]`` — a
    masked sublane sum (exactly one row matches, so values are copied)."""
    x = blk[e].astype(jnp.float32)
    sub = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.sum(jnp.where(sub == off, x, 0.0), axis=0, keepdims=True)


def resolve_bwd_impl(bwd_impl: str, e_tile: int | None) -> tuple[str, int]:
    """Validate a differentiable entry point's ``bwd_impl`` knob and
    resolve the csr entry-tile default — shared by bloom_embed_pallas
    and bloom_decode_pallas so the two public APIs cannot drift."""
    if bwd_impl not in ("dense", "csr"):
        raise ValueError(f"bwd_impl must be 'dense' or 'csr', "
                         f"got {bwd_impl!r}")
    if e_tile is None:
        from repro.kernels.bloom_csr import CSR_E_TILE
        e_tile = CSR_E_TILE
    return bwd_impl, e_tile


def pad_axis(x: jnp.ndarray, axis: int, multiple: int,
             value=0) -> jnp.ndarray:
    """Right-pad `axis` of x to a multiple of `multiple` with `value`."""
    pad = (-x.shape[axis]) % multiple
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def onehot_count(ids: jnp.ndarray, n: int, base=0) -> jnp.ndarray:
    """counts[r, c] = #{j : ids[r, j] == base + c} as float32.

    The shared building block of every backward kernel's scatter-add:
    built from k iota-compares over a (rows, n) tile in VMEM/registers —
    the dense one-hot never exists in HBM.  Out-of-range ids (e.g. the -1
    padding sentinel) simply never match.  `base` offsets the class axis
    for m-tiled grids.
    """
    rows, k = ids.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (rows, n), 1) + base
    w = (iota == ids[:, 0:1]).astype(jnp.float32)
    for j in range(1, k):
        w = w + (iota == ids[:, j:j + 1]).astype(jnp.float32)
    return w
