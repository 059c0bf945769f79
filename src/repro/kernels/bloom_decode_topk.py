"""Pallas TPU kernel: fused Bloom vocabulary recovery + streaming top-k
(the serving hot path — paper Fig. 3 right, DESIGN.md §4/§5).

The unfused serving decode writes the full (B, d) recovered-score matrix to
HBM and reads it back for jax.lax.top_k — 2 * B * d * 4 bytes that dominate
decode cost at LLM vocab scale (qwen3-4b: d = 151 936).  This kernel never
materializes the score matrix: it streams (k, v_tile) tiles of the
transposed hash matrix through the grid, recovers each (Bt, Vt) score tile
in VMEM from the resident (Bt, m) log-prob block, and folds it into a
running per-batch top-k held in VMEM scratch.  HBM traffic drops to

    B*m*4 (logp) + d*k*4 (H) + B*topk*8 (out)        [>= 3.8x fewer bytes
                                                      than decode-then-topk
                                                      at qwen3-4b shapes]

  grid = (nB, nV)          — vocab axis innermost
  logp — block (Bt, m) of the (nB, Bt, m) row blocks at (b, 0, 0)
         (VMEM-resident across the vocab sweep; widened to f32 scratch)
  H^T  — block (k, Vt)  at (0, v)  (vocab ids on lanes)
  outs — values / ids (Bt, topk) at (b, 0, 0), written once at the last
         vocab step
  scratch — running best values/ids (Bt, 128), reset at v == 0

Mosaic gathers lanes only inside one (8, 128) vector register and has no
in-kernel ``top_k``, so the score tile is built 128 ids at a time by a
two-level gather (gather_scores) and merged by an iterative max-extract
that breaks ties toward the lowest id, exactly as ``jax.lax.top_k`` on the
materialized scores; each vocab id enters the stream exactly once, so no
dedup pass is needed.

**Row-skipping grid (serving slot pools, DESIGN.md §8).**  A continuous-
batching pool at partial occupancy decodes dead slot rows; the dense grid
still streams every (logp row-block, H vocab tile) pair for them.  With
``active`` given, a slot-occupancy-prefetched grid
(``pltpu.PrefetchScalarGridSpec``) skips the HBM traffic of fully-inactive
row blocks: the prefetched per-block occupancy drives *data-dependent
index maps* that pin an inactive block's logp/H block indices to the
previously-resident blocks, so the Pallas pipeline issues NO new copies
for them (a revisited block index is never re-fetched); the kernel body
skips the fold under ``pl.when`` and emits (-inf, 0) for skipped rows —
exactly the post-hoc masking ``io.recover_topk`` applies anyway.  Modeled
HBM bytes drop from ``nB*(Bt*m*4 + d*k*4)`` to ``nA*(Bt*m*4 + d*k*4)``
(+ the B*topk*8 output either way) where nA = #row-blocks containing at
least one live slot — bytes scale with occupancy instead of pool size
(bench_kernels.py commits the occupancy sweep; CI gates >=1.5x fewer
bytes at <=50% occupancy).

**Quantized logp + in-kernel hashing (DESIGN.md §13).**  ``table_dtype``
stores the resident (Bt, m) block in bf16/int8/fp8 in HBM; it is widened
to f32 once per row block in VMEM, int8 with ONE per-batch-row scale
multiply.  That alone cannot beat the fp32 row by the
gated 3x: at serving batch sizes the ``d*k*4`` H stream dominates (2.4 MB
vs 0.24 MB of logp at qwen3-4b/B=8).  So the quantized path also drops H
entirely: ``hash_spec=(d, k, seed)`` re-derives every vocab tile's hash
indices IN-KERNEL from the tile's id iota via enhanced double hashing —
bit-identical to core.hashing.double_hash (and therefore to the cached
(d, k) matrix for any on-the-fly spec), at zero HBM bytes.  Identity
specs (m == d, k == 1) keep the explicit-H path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hashing, quant
from repro.kernels.common import pad_axis, resolve_interpret


def modeled_hbm_bytes(active, b_tile: int, *, m: int, d: int, k: int,
                      topk: int, logp_itemsize: int = 4,
                      inkernel_hash: bool = False,
                      row_scales: bool = False) -> int:
    """Analytic HBM bytes of one row-skipping decode-topk call for a
    given slot-occupancy mask — the SINGLE source for the occupancy rows
    in benchmarks/bench_kernels.py and the serving byte audits, so the
    bytes model can never drift from the grid it describes.

    Per VISITED row block the grid streams the (b_tile, m) logp block at
    ``logp_itemsize`` bytes/element (4 = legacy f32; the table_dtype knob
    sets 2/1/1 for bf16/int8/fp8) plus one full (d, k) i32 sweep of H
    (vocab axis innermost => H is re-streamed per block) — unless
    ``inkernel_hash``, where the hash indices are re-derived from the
    tile iota at zero HBM cost.  ``row_scales`` adds the (b_tile,) f32
    int8 dequant scales per visited block.  Blocks with no live slot are
    pinned to resident blocks and fetch nothing.  The (B, topk) f32+i32
    outputs are flushed for every block, live or dead.  A dense (no
    ``active``) grid is the all-ones mask.
    """
    act = np.asarray(active, bool).ravel()
    B = act.shape[0]
    pad = (-B) % b_tile
    if pad:
        act = np.concatenate([act, np.zeros(pad, bool)])
    n_visited = int(act.reshape(-1, b_tile).any(axis=1).sum())
    per_block = b_tile * m * logp_itemsize
    if not inkernel_hash:
        per_block += d * k * 4
    if row_scales:
        per_block += b_tile * 4
    return int(n_visited * per_block + B * topk * 8)


# Lane width of a TPU vector register: the in-kernel gather works on
# (rows, 128) vregs, so the m axis of the resident block and every vocab
# tile are padded to multiples of it.
LANES = 128
LANE_SHIFT = 7                                  # log2(LANES)

# Sentinel id of the running best's unused lanes: larger than every vocab
# id, so it loses every lowest-id tie-break and never leaks out.
_NO_ID = np.iinfo(np.int32).max


def chunk_indices(h_ref, base, c, hash_spec, k):
    """k (1, LANES) int32 hash-index rows of the LANES vocab ids starting
    at global id ``base`` — lane chunk ``c`` of the current vocab tile.

    Explicit-H path: ``h_ref`` is the (k, v_tile) block of the transposed
    hash matrix (ids on lanes).  ``hash_spec=(m, k, c1, c2)`` instead
    re-derives the indices from the id iota via enhanced double hashing —
    the exact arithmetic of core.hashing.double_hash, with the two mixed
    salts baked in as static scalars (hashing.double_hash_salts)."""
    if hash_spec is None:
        off = pl.multiple_of(c * LANES, LANES)
        return [h_ref[j:j + 1, pl.ds(off, LANES)] for j in range(k)]
    m, k, c1, c2 = hash_spec
    vid = (jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
           + base).astype(jnp.uint32)
    h1 = hashing.splitmix32(vid ^ np.uint32(c1)) % np.uint32(m)
    h2 = hashing.splitmix32(vid ^ np.uint32(c2)) \
        % np.uint32(max(m - 1, 1)) + np.uint32(1)
    out = []
    for j in range(k):
        tri = (j ** 3 - j) // 6 % m
        hj = (h1 + np.uint32(j) * h2 + np.uint32(tri)) % np.uint32(m)
        out.append(hj.astype(jnp.int32))
    return out


def _lane_gather(x, idx):
    """out[b, l] = x[b, idx[b, l]] within one (rows, LANES) register: the
    gather form Mosaic lowers (tpu.dynamic_gather along lanes).  Spelled
    as lax.gather because jnp.take_along_axis drops size-1 row axes into
    a form the lowering refuses."""
    dn = jax.lax.GatherDimensionNumbers(
        offset_dims=(), collapsed_slice_dims=(1,), start_index_map=(1,),
        operand_batching_dims=(0,), start_indices_batching_dims=(0,))
    return jax.lax.gather(x, idx[..., None], dn, slice_sizes=(1, 1),
                          mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def gather_scores(lp_ref, idxs):
    """(Bt, LANES) Eq. 3 scores sum_j lp[b, idxs[j]] for one lane chunk.

    ``lp_ref`` is the resident (Bt, mp) f32 log-prob block, mp a multiple
    of LANES.  Mosaic gathers lanes only within one vector register, so
    the k-gather is two-level: sweep the mp / LANES source registers and,
    for each, gather every index's lane (a lane gather on one vreg)
    and keep it where the index's register matches.  Exactly one register
    matches per index, so each gathered value is copied, never combined,
    and the k terms are summed in j order as in the XLA oracle."""
    bt, mp = lp_ref.shape
    rows = [jnp.broadcast_to(i >> LANE_SHIFT, (bt, LANES)) for i in idxs]
    lanes = [jnp.broadcast_to(i & (LANES - 1), (bt, LANES)) for i in idxs]

    def body(r, accs):
        src = lp_ref[:, pl.ds(pl.multiple_of(r * LANES, LANES), LANES)]
        return tuple(
            jnp.where(rows[j] == r, _lane_gather(src, lanes[j]), accs[j])
            for j in range(len(idxs)))

    zero = jnp.zeros((bt, LANES), jnp.float32)
    accs = jax.lax.fori_loop(0, mp // LANES, body, (zero,) * len(idxs))
    scores = accs[0]
    for a in accs[1:]:
        scores = scores + a
    return scores


def load_resident(logp_ref, s_ref, lp_ref):
    """Widen the (Bt, mp) logp block into the f32 gather scratch.  int8
    dequantizes HERE with one per-row scale multiply, before the gather,
    so the gathered values (and tie patterns) are bit-identical to the
    XLA dequantize-then-decode oracle."""
    x = logp_ref[...].astype(jnp.float32)
    if s_ref is not None:
        x = x * s_ref[...]                          # s (Bt, 1)
    lp_ref[...] = x


def _merge_topk(best_v, best_i, sc_ref, iv, v_tile, topk):
    """Fold the (Bt, v_tile) score tile into the running best.

    Iterative max-extract over [best, tile]: each round takes the row max
    and, among the entries equal to it, the LOWEST id — the tie order of
    jax.lax.top_k on the materialized score vector.  Unused lanes of the
    best hold (-inf, _NO_ID); every real id is lower, so a sentinel never
    wins while a real candidate remains (every tile of ids ascends, and
    the first tile alone holds >= topk real ids)."""
    bt = sc_ref.shape[0]
    gid = jax.lax.broadcasted_iota(jnp.int32, (bt, v_tile), 1) + iv * v_tile
    cand_v = jnp.concatenate([best_v[...], sc_ref[...]], axis=1)
    cand_i = jnp.concatenate([best_i[...], gid], axis=1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (bt, LANES), 1)
    new_v = jnp.full((bt, LANES), -jnp.inf, jnp.float32)
    new_i = jnp.full((bt, LANES), _NO_ID, jnp.int32)
    alive = jnp.ones(cand_v.shape, jnp.bool_)
    for t in range(topk):
        masked = jnp.where(alive, cand_v, -jnp.inf)
        mx = jnp.max(masked, axis=1, keepdims=True)
        sel = jnp.min(jnp.where(alive & (masked == mx), cand_i, _NO_ID),
                      axis=1, keepdims=True)
        new_v = jnp.where(lane == t, mx, new_v)
        new_i = jnp.where(lane == t, sel, new_i)
        alive = alive & (cand_i != sel)
    best_v[...] = new_v
    best_i[...] = new_i


def _fold_tile(logp_ref, s_ref, h_ref, vals_ref, ids_ref, lp_ref, sc_ref,
               best_v, best_i, *, iv, topk, v_tile, d, k, hash_spec):
    """One (row-block, vocab-tile) fold of the streaming top-k — shared
    by the dense and the row-skipping grids."""
    @pl.when(iv == 0)
    def _():
        load_resident(logp_ref, s_ref, lp_ref)
        best_v[...] = jnp.full(best_v.shape, -jnp.inf, jnp.float32)
        best_i[...] = jnp.full(best_i.shape, _NO_ID, jnp.int32)

    bt = sc_ref.shape[0]

    def chunk(c, carry):
        base = iv * v_tile + c * LANES
        scores = gather_scores(lp_ref,
                               chunk_indices(h_ref, base, c, hash_spec, k))
        gid = jax.lax.broadcasted_iota(jnp.int32, (bt, LANES), 1) + base
        sc_ref[:, pl.ds(pl.multiple_of(c * LANES, LANES), LANES)] = \
            jnp.where(gid < d, scores, -jnp.inf)    # mask vocab padding
        return carry

    jax.lax.fori_loop(0, v_tile // LANES, chunk, 0)
    _merge_topk(best_v, best_i, sc_ref, iv, v_tile, topk)

    @pl.when(iv == pl.num_programs(1) - 1)
    def _():
        vals_ref[...] = best_v[:, :topk]
        ids_ref[...] = best_i[:, :topk]


def _split_refs(refs, has_scales, hash_spec):
    """(logp[, s][, h], vals, ids, lp, sc, best_v, best_i) positional
    unpack for the dense/skip kernels' variable operand lists."""
    refs = list(refs)
    logp_ref = refs.pop(0)
    s_ref = refs.pop(0) if has_scales else None
    h_ref = refs.pop(0) if hash_spec is None else None
    return (logp_ref, s_ref, h_ref, *refs)


def _kernel(*refs, topk, v_tile, d, k, has_scales, hash_spec):
    _fold_tile(*_split_refs(refs, has_scales, hash_spec),
               iv=pl.program_id(1), topk=topk, v_tile=v_tile, d=d, k=k,
               hash_spec=hash_spec)


def _kernel_skip(occ_ref, pin_ref, *refs, topk, v_tile, d, k, has_scales,
                 hash_spec):
    """Row-skipping variant: ``occ_ref``/``pin_ref`` are the scalar-
    prefetched per-block occupancy / logp-block pin arrays (also consumed
    by the data-dependent index maps).  Inactive blocks never touch HBM:
    their logp/H block indices revisit resident blocks (no copy), the fold
    is skipped, and the output block — which IS flushed for every b — is
    written as (-inf, 0), matching recover_topk's dead-row masking."""
    split = _split_refs(refs, has_scales, hash_spec)
    vals_ref, ids_ref = split[3], split[4]
    ib = pl.program_id(0)
    iv = pl.program_id(1)
    act = occ_ref[ib] > 0

    @pl.when(act)
    def _():
        _fold_tile(*split, iv=iv, topk=topk, v_tile=v_tile, d=d, k=k,
                   hash_spec=hash_spec)

    @pl.when(jnp.logical_not(act) & (iv == pl.num_programs(1) - 1))
    def _():
        vals_ref[...] = jnp.full(vals_ref.shape, -jnp.inf,
                                 vals_ref.dtype)
        ids_ref[...] = jnp.zeros(ids_ref.shape, ids_ref.dtype)


def block_occupancy(active: jnp.ndarray, b_tile: int):
    """active (B,) bool -> (occ, pin), the scalar-prefetch operands of the
    row-skipping grid, for B padded to a multiple of b_tile.

    occ (nB,) int32 — 1 iff the row block holds >=1 live slot.
    pin (nB,) int32 — logp block to map block b's fetch to: b itself when
    active, else the nearest active block at-or-before b (still resident
    when the pipeline reaches b — revisit, no copy), else the FIRST
    active block (leading dead blocks prefetch the block the first live
    sweep needs anyway, so even a drained low-slot prefix issues no dead
    logp fetch).  All-dead pools pin to 0 (one unavoidable fetch; the
    engine never decodes an empty pool).
    """
    act = pad_axis(active.astype(jnp.int32), 0, b_tile)
    blk = act.reshape(-1, b_tile).max(axis=1)
    idx = jnp.arange(blk.shape[0], dtype=jnp.int32)
    cand = jnp.where(blk > 0, idx, -1)
    before = jax.lax.cummax(cand, axis=0)
    first_active = jnp.argmax(blk > 0).astype(jnp.int32)  # 0 if none
    pin = jnp.where(before >= 0, before, first_active).astype(jnp.int32)
    return blk.astype(jnp.int32), pin


@functools.partial(jax.jit,
                   static_argnames=("topk", "b_tile", "v_tile", "interpret",
                                    "table_dtype", "hash_spec"))
def bloom_decode_topk_pallas(logp: jnp.ndarray, H: jnp.ndarray | None,
                             topk: int,
                             b_tile: int = 8, v_tile: int = 2048,
                             interpret: bool | None = None,
                             active: jnp.ndarray | None = None,
                             table_dtype: str | None = None,
                             hash_spec: tuple[int, int, int] | None = None):
    """logp (B, m) float; H (d, k) int32 -> (values, ids), each (B, topk).

    values[b] are the topk largest Eq. 3 scores over the original vocab,
    descending; ids[b] the corresponding item/token ids.  The (B, d) score
    matrix is never written to HBM.

    ``active`` (B,) bool selects the row-skipping occupancy grid: rows in
    a fully-inactive b_tile block are skipped at the HBM level (no logp /
    H tile fetches — see module docstring) and return (-inf, 0); rows
    sharing a block with a live slot are computed normally, identical to
    the dense grid (the caller masks dead rows regardless —
    io.recover_topk).

    ``table_dtype`` (DESIGN.md §13) stores the resident logp block in a
    narrower dtype (int8: per-row symmetric scales, dequantized on the
    score tile).  ``hash_spec=(d, k, seed)`` drops the H operand and
    re-derives hash indices in-kernel (bit-identical to
    core.hashing.double_hash for on-the-fly specs); H may then be None.
    """
    interpret = resolve_interpret(interpret)
    B, m = logp.shape
    if hash_spec is not None:
        d, k, seed = hash_spec
        c1, c2 = hashing.double_hash_salts(seed)
        kern_hash = (m, k, c1, c2)
        H = None
    else:
        d, k = H.shape
        kern_hash = None
    if not (0 < topk <= min(d, LANES)):
        raise ValueError(f"need 0 < topk <= min(d, {LANES}), got "
                         f"topk={topk} d={d}")
    b_tile = min(b_tile, B)
    # the first tile seeds the running best with >= topk real ids; tiles
    # are whole lane chunks
    v_tile = max(min(v_tile, d), topk)
    v_tile += (-v_tile) % LANES

    table_dtype = quant.resolve_table_dtype(table_dtype)
    scales = None
    if table_dtype is not None:
        logp, scales = quant.quantize_table(logp, table_dtype)

    # (nB, b_tile, mp) blocks: each row block is a whole trailing (b_tile,
    # mp) slab, which every dtype's tiling accepts at any b_tile
    logp = pad_axis(pad_axis(logp, 0, b_tile), 1, LANES)
    Bp, mp = logp.shape
    nB = Bp // b_tile
    logp = logp.reshape(nB, b_tile, mp)
    dp = d + ((-d) % v_tile)                   # padded ids masked via d
    if H is not None:
        H = pad_axis(H, 0, v_tile).T           # (k, dp): ids on lanes
    grid = (nB, dp // v_tile)
    has_scales = scales is not None
    if has_scales:
        scales = pad_axis(scales.astype(jnp.float32), 0, b_tile)
        scales = scales.reshape(nB, b_tile, 1)

    out_shape = [
        jax.ShapeDtypeStruct((nB, b_tile, topk), jnp.float32),
        jax.ShapeDtypeStruct((nB, b_tile, topk), jnp.int32),
    ]
    scratch_shapes = [
        pltpu.VMEM((b_tile, mp), jnp.float32),      # widened logp block
        pltpu.VMEM((b_tile, v_tile), jnp.float32),  # score tile
        pltpu.VMEM((b_tile, LANES), jnp.float32),   # running best values
        pltpu.VMEM((b_tile, LANES), jnp.int32),     # running best ids
    ]
    kwargs = dict(topk=topk, v_tile=v_tile, d=d, k=k, has_scales=has_scales,
                  hash_spec=kern_hash)
    row_blk = (None, b_tile, mp)
    out_blk = (None, b_tile, topk)

    if active is None:
        in_specs = [pl.BlockSpec(row_blk, lambda b, v: (b, 0, 0))]
        operands = [logp]
        if has_scales:
            in_specs.append(pl.BlockSpec((None, b_tile, 1),
                                         lambda b, v: (b, 0, 0)))
            operands.append(scales)
        if H is not None:
            in_specs.append(pl.BlockSpec((k, v_tile), lambda b, v: (0, v)))
            operands.append(H)
        vals, ids = pl.pallas_call(
            functools.partial(_kernel, **kwargs),
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec(out_blk, lambda b, v: (b, 0, 0)),
                pl.BlockSpec(out_blk, lambda b, v: (b, 0, 0)),
            ],
            out_shape=out_shape,
            scratch_shapes=scratch_shapes,
            interpret=interpret,
        )(*operands)
        return vals.reshape(Bp, topk)[:B], ids.reshape(Bp, topk)[:B]

    occ, pin = block_occupancy(active, b_tile)
    nv_last = grid[1] - 1
    in_specs = [
        # inactive block: revisit the pinned logp block and the H tile
        # left resident by the previous sweep (nv_last) — a revisited
        # block index issues no copy in the Pallas pipeline.  Leading
        # dead blocks (pin points FORWARD to the first active block)
        # instead prefetch tile 0, the tile that first live sweep starts
        # with, so they too fetch nothing the live sweeps would not
        # fetch anyway.
        pl.BlockSpec(row_blk, lambda b, v, occ, pin: (pin[b], 0, 0)),
    ]
    operands = [logp]
    if has_scales:
        in_specs.append(pl.BlockSpec((None, b_tile, 1),
                                     lambda b, v, occ, pin: (pin[b], 0, 0)))
        operands.append(scales)
    if H is not None:
        in_specs.append(pl.BlockSpec(
            (k, v_tile),
            lambda b, v, occ, pin:
            (0, jnp.where(occ[b] > 0, v,
                          jnp.where(pin[b] > b, 0, nv_last)))))
        operands.append(H)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec(out_blk, lambda b, v, occ, pin: (b, 0, 0)),
            pl.BlockSpec(out_blk, lambda b, v, occ, pin: (b, 0, 0)),
        ],
        scratch_shapes=scratch_shapes,
    )
    vals, ids = pl.pallas_call(
        functools.partial(_kernel_skip, **kwargs),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(occ, pin, *operands)
    return vals.reshape(Bp, topk)[:B], ids.reshape(Bp, topk)[:B]
