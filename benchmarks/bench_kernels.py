"""Kernel microbenchmarks: the Bloom Pallas kernel suite (fwd, bwd and the
fused decode-topk) at production shapes, with analytic TPU-v5e byte/time
models (this box is CPU — wall time of interpret mode is meaningless;
bytes-derived HBM time is the metric).

Every row couples the analytic bytes model of the kernel's HBM traffic at
the PRODUCTION shape with a numeric oracle check (kernel vs the pure-jnp
XLA reference) at a shape small enough for interpret mode; `check_*` fields
record the checked shape when it is scaled down.

``python -m benchmarks.bench_kernels --quick`` regenerates the committed
``BENCH_kernels.json``; ``--check`` instead compares fresh errors/ratios
against the committed file and exits non-zero on regression (wired into
CI).  The serving acceptance bar lives in the `decode_topk` rows:
``hbm_ratio`` = decode-then-top_k bytes / fused bytes must stay >= 3 at the
qwen3-4b shape.  The training acceptance bar lives in the ``*.bwd.csr``
rows (uniform + collision-heavy skew variants): the CSR-binned backward
must model >= MIN_EMBED_CSR_RATIO / MIN_DECODE_CSR_RATIO fewer bytes than
the dense-sweep rows it replaces; every ``*.bwd`` row carries
``bytes_ideal`` (the single-pass floor of the op AS A SCATTER-ADD —
embed's includes the grad table's read-modify-write) and
``bwd_bytes_ratio`` = bytes / bytes_ideal, which the embed CSR rows
legitimately push below 1.0 (sorting turns the RMW scatter into
write-once output runs — see the embed.bwd comment in run()).

The quantized-table acceptance bar (DESIGN.md §13) lives in the
``*.embed.fwd.{fp32,bf16,int8,fp8}`` and ``*.decode_topk.{bf16,int8,fp8}``
rows: the int8 rows must model >= MIN_INT8_VS_FP32 fewer total bytes than
their fp32 twin and >= MIN_INT8_VS_BF16 fewer than bf16 (table stream for
embed — the activations are bf16 either way; whole row for decode-topk,
whose quantized path also drops the (d, k) hash stream by re-deriving
indices in-kernel).  All byte widths are single-sourced from dtype
itemsize (core.quant.table_itemsize / ndarray.dtype.itemsize) — no bare
``* 2`` / ``* 4`` literals — so a storage-dtype change cannot silently
desync the models.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant
from repro.core.bloom import BloomSpec
from repro.kernels import ops, ref
# M_TILE is single-sourced from the kernels so the bwd bytes models
# cannot drift from the m-tile the backward grids actually run with
from repro.kernels.common import BWD_M_TILE as M_TILE
from repro.kernels.bloom_ce import bloom_ce_pallas
from repro.kernels.bloom_csr import (modeled_decode_bwd_csr_bytes,
                                     modeled_embed_bwd_csr_bytes)
from repro.kernels.bloom_decode import bloom_decode_pallas
from repro.kernels.bloom_decode_topk import (bloom_decode_topk_pallas,
                                             modeled_hbm_bytes)
from repro.kernels.bloom_embed import bloom_embed_pallas
from repro.serving.control import plan_compaction

HBM_BW = 819e9
JSON_PATH = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_kernels.json"
TOPK = 16
B_DECODE = 8
# serving-pool shape for the row-skipping occupancy sweep: 64 slots in
# b_tile=8 row blocks (8 blocks) — the scale where block skipping pays
B_POOL = 64
BT_POOL = 8
SPH_POOL = 16         # slots per host shard in the compaction row
MIN_OCC_RATIO = 1.5   # >= 1.5x fewer modeled bytes at <= 50% occupancy
# compaction acceptance (ISSUE 4): the densified scattered pool must
# model within 1.1x of the globally-dense pool's bytes
MAX_COMPACT_VS_DENSE = 1.1
# CSR-binned backward acceptance (ISSUE 5): the binned scatter-add must
# model >= these factors fewer HBM bytes than the dense m-tile sweep it
# replaces (both at the production shape)
MIN_EMBED_CSR_RATIO = 3.0
MIN_DECODE_CSR_RATIO = 10.0
# quantized-table acceptance (ISSUE 9, DESIGN.md §13): the int8 rows
# must model >= these factors fewer HBM bytes than their fp32 / bf16
# twins (embed compares the table stream against bf16 — activations are
# bf16 on both; decode-topk compares whole rows)
MIN_INT8_VS_FP32 = 3.0
MIN_INT8_VS_BF16 = 1.8
# itemsizes, single-sourced (satellite of ISSUE 9): every bytes model
# below derives widths from these or from the benched array's own dtype
IS_F32 = jnp.dtype(jnp.float32).itemsize
IS_I32 = jnp.dtype(jnp.int32).itemsize
# fused top-k emits (values f32, ids i32) per kept element
IS_TOPK_PAIR = IS_F32 + IS_I32
# quantized embed rows: sub-f32 storage emits bf16 activations (the
# serving compute dtype); fp32 storage emits fp32
QUANT_EMBED_SWEEP = (("float32", "fp32"), ("bfloat16", "bf16"),
                     ("int8", "int8"), ("fp8_e4m3", "fp8"))
QUANT_TOPK_SWEEP = (("bfloat16", "bf16"), ("int8", "int8"),
                    ("fp8_e4m3", "fp8"))


def _cases():
    # (name, d, m, k, D, tokens)
    return [
        ("qwen3-4b", 151_936, 30_464, 4, 2560, 4096),
        ("qwen1.5-0.5b", 151_936, 30_464, 4, 1024, 4096),
        ("pixtral-12b", 131_072, 26_112, 4, 5120, 2048),
    ]


def _row(name, tokens, bytes_moved, err, **extra):
    return {"bench": "kernels", "name": name, "tokens": tokens,
            "bytes": bytes_moved, "max_err": err,
            "tpu_us_model": 1e6 * bytes_moved / HBM_BW, **extra}


def _max_err(a, b):
    return float(jnp.abs(jnp.asarray(a, jnp.float32)
                         - jnp.asarray(b, jnp.float32)).max())


def run(quick: bool = True):
    rows = []
    key = jax.random.PRNGKey(0)
    for name, d, m, k, D, T in _cases():
        # Bytes models are computed at the PRODUCTION shape (d, m, k, D, T
        # from _cases()).  Interpret-mode Pallas executes the grid in
        # Python, so the numeric oracle CHECK runs at a clamped token
        # count, recorded in check_* fields — never in the bytes model.
        Tc = min(T, 64 if quick else 256)
        spec = BloomSpec(d=d, m=m, k=k)
        table = jax.random.normal(key, (m, D), jnp.bfloat16)
        tokens = jax.random.randint(key, (1, Tc), 0, d)
        idx = spec.indices_for(tokens.reshape(-1))
        n_mtiles = -(-m // M_TILE)   # outer m-tile sweeps of the bwd grids
        its_tbl = table.dtype.itemsize   # serving table dtype (bf16)
        its_idx = idx.dtype.itemsize     # int32 hash/index streams

        # ---- embed fwd: k rows of D bf16 per token + output write --------
        got = ops.bloom_embed(table, tokens, spec)[0]
        want = ref.bloom_embed_ref(table, idx)
        bytes_fwd = T * (k * D * its_tbl + D * its_tbl) + T * k * its_idx
        row = _row(f"{name}.embed.fwd", T, bytes_fwd,
                   _max_err(got, want), check_tokens=Tc)
        rows.append(row)

        # ---- embed fwd, quantized tables (DESIGN.md §13): the same
        # k-row gather with the table stored narrow in HBM.  int8 adds
        # the scale stream: one f32 per table row, gathered host-side
        # into the (T, k) scalar-prefetch operand (written once, read
        # once by the grid).  fp32/bf16 emit activations in their own
        # dtype; the sub-f32 dtypes emit bf16 (the serving compute
        # dtype).  ``table_bytes`` isolates the table+scale stream —
        # the int8 vs bf16 gate compares it (whole-row totals share the
        # bf16 activation term, diluting the table win below the bar).
        # Numeric check: kernel on the narrow table vs the XLA oracle on
        # the DEQUANTIZED table — identical values by construction (the
        # kernel dequantizes on the VMEM-resident tile, MXU accumulation
        # stays f32).
        tbl_master = table.astype(jnp.float32)
        qbytes = {}
        for td, alias in QUANT_EMBED_SWEEP:
            its = quant.table_itemsize(td)
            out_is = its if td in ("float32", "bfloat16") else \
                jnp.dtype(jnp.bfloat16).itemsize
            table_bytes = T * k * D * its
            if td == "int8":
                table_bytes += m * IS_F32 + 2 * T * k * IS_F32
            bytes_q = table_bytes + T * D * out_is + T * k * its_idx
            qbytes[alias] = (bytes_q, table_bytes)
            q, s = quant.quantize_table(tbl_master, td)
            got = bloom_embed_pallas(tbl_master, idx, table_dtype=td,
                                     out_dtype=jnp.float32)
            want = ref.bloom_embed_ref(quant.dequantize_table(q, s), idx)
            extra = {}
            if alias != "fp32":
                extra["vs_fp32_ratio"] = round(
                    qbytes["fp32"][0] / bytes_q, 4)
            if alias == "int8":
                extra["vs_bf16_ratio"] = round(
                    qbytes["bf16"][1] / table_bytes, 4)
            row = _row(f"{name}.embed.fwd.{alias}", T, bytes_q,
                       _max_err(got, want), check_tokens=Tc,
                       table_dtype=td, table_bytes=table_bytes, **extra)
            rows.append(row)

        # ---- embed bwd: blocked one-hot contraction.  The kernel sweeps
        # the m axis in M_TILE blocks and re-reads g/idx from HBM on every
        # sweep; the f32 grad table is written exactly once (blocks are
        # zero-initialized in VMEM).  `bytes_ideal` is the single-pass
        # SCATTER-ADD floor (one g read + the grad table's RMW read+write
        # — the 2*m*D*4 term — as a true data-dependent scatter pays);
        # `bwd_bytes_ratio` = bytes / that floor.  NOTE the CSR rows land
        # BELOW 1.0 on this ratio: binning sorts the scatter into
        # write-once output runs, so it never pays the RMW read — beating
        # the scatter formulation's floor is the point, not a modeling
        # error.  Numeric check runs jax.grad through the custom-VJP at a
        # reduced (tokens, d_model) shape.
        Tb = min(Tc, 16)
        idx_b = idx[:Tb]
        tbl32 = table[:, :min(D, 512)].astype(jnp.float32)
        cot = jax.random.normal(key, (Tb, tbl32.shape[1]))
        g_pal = jax.grad(lambda t: jnp.sum(
            bloom_embed_pallas(t, idx_b, interpret=True) * cot))(tbl32)
        g_ref = jax.grad(lambda t: jnp.sum(
            ref.bloom_embed_ref(t, idx_b) * cot))(tbl32)
        bytes_bwd = n_mtiles * (T * D * IS_F32 + T * k * its_idx) \
            + m * D * IS_F32
        bytes_bwd_ideal = T * D * IS_F32 + 2 * m * D * IS_F32 \
            + T * k * its_idx
        rows.append(_row(f"{name}.embed.bwd", T, bytes_bwd,
                         _max_err(g_pal, g_ref),
                         bytes_ideal=bytes_bwd_ideal,
                         bwd_bytes_ratio=round(bytes_bwd
                                               / bytes_bwd_ideal, 4),
                         check_tokens=Tb, check_dmodel=tbl32.shape[1]))

        # ---- embed bwd CSR: the binned scatter-add (bwd_impl="csr").
        # The production bytes model is distribution-INDEPENDENT: the
        # kernel DMAs exactly the E = T*k live cotangent rows whatever
        # the hash draw (pad slots are gated off), so the uniform and
        # collision-heavy rows commit the SAME bytes — the skew variant
        # exists to pin numeric correctness when every entry piles into
        # one m-tile (long multi-tile segment + all-empty pad tiles).
        # Numeric checks run jax.grad through the custom VJP at a scaled
        # (tokens, m, d_model) shape, recorded in check_* fields.
        bytes_bwd_csr = modeled_embed_bwd_csr_bytes(T, k, D, m)
        m_chk = 4096
        tblc = jax.random.normal(key, (m_chk, tbl32.shape[1]))
        for variant, hi in (("", m_chk), (".skew", min(M_TILE, m_chk))):
            idx_c = jax.random.randint(jax.random.fold_in(key, 11),
                                       (Tb, k), 0, hi)
            cot_c = jax.random.normal(jax.random.fold_in(key, 12),
                                      (Tb, tbl32.shape[1]))
            g_pal = jax.grad(lambda t: jnp.sum(
                bloom_embed_pallas(t, idx_c, interpret=True,
                                   bwd_impl="csr") * cot_c))(tblc)
            g_ref = jax.grad(lambda t: jnp.sum(
                ref.bloom_embed_ref(t, idx_c) * cot_c))(tblc)
            rows.append(_row(
                f"{name}.embed.bwd.csr{variant}", T, bytes_bwd_csr,
                _max_err(g_pal, g_ref),
                bytes_ideal=bytes_bwd_ideal,
                bwd_bytes_ratio=round(bytes_bwd_csr / bytes_bwd_ideal, 4),
                vs_dense_ratio=round(bytes_bwd / bytes_bwd_csr, 4),
                skew="collision_heavy" if variant else "uniform",
                check_tokens=Tb, check_m=m_chk,
                check_dmodel=tbl32.shape[1]))

        # ---- ce fwd: ONE read of the (T, m) f32 logits row + loss/lse ----
        logits = jax.random.normal(key, (Tc, m), jnp.float32)
        labels = jax.random.randint(key, (Tc,), 0, d)
        got = ops.bloom_ce(logits, labels, spec)
        from repro.core import losses
        want = losses.bloom_xent_label(spec, logits, labels)
        bytes_ce_fwd = T * m * IS_F32 + T * k * its_idx + 2 * T * IS_F32
        rows.append(_row(f"{name}.ce.fwd", T, bytes_ce_fwd,
                         _max_err(got, want), check_tokens=Tc))

        # ---- ce bwd: lse residual — read the row once, write dz once
        # (token-blocked grid, no m-tiling: the model IS the actual
        # kernel traffic here) ---------------------------------------------
        h = spec.indices_for(labels)
        cot = jax.random.normal(key, (Tc,))
        g_pal = jax.grad(lambda z: jnp.sum(
            bloom_ce_pallas(z, h, interpret=True) * cot))(logits)
        g_ref = jax.grad(lambda z: jnp.sum(
            ref.bloom_ce_ref(z, h) * cot))(logits)
        # ce.bwd IS the floor already (ISSUE 5 satellite: emit the ideal
        # + ratio for it too, so every *.bwd row carries the same audit
        # columns): one logits-row read + one dz write is irreducible
        bytes_ce_bwd = 2 * T * m * IS_F32 + T * (k + 2) * IS_F32
        rows.append(_row(f"{name}.ce.bwd", T, bytes_ce_bwd,
                         _max_err(g_pal, g_ref),
                         bytes_ideal=bytes_ce_bwd, bwd_bytes_ratio=1.0,
                         check_tokens=Tc))

        # ---- decode fwd: logp rows + (d, k) hash matrix + (B, d) scores --
        B = B_DECODE
        logp = jax.nn.log_softmax(jax.random.normal(key, (B, m)))
        scores = ops.bloom_decode(logp, spec)
        H = ops.cached_hash_matrix(spec)
        want_scores = ref.bloom_decode_ref(logp, H)
        bytes_dec = B * m * IS_F32 + d * k * its_idx + B * d * IS_F32
        rows.append(_row(f"{name}.decode", B, bytes_dec,
                         _max_err(scores, want_scores)))

        # ---- decode bwd: blocked scatter-add of the (B, d) cotangent;
        # like embed.bwd, the m-tile sweep re-reads g/H per M_TILE block
        # and writes dlogp once.  Full-shape MACs (B*d*m) are prohibitive
        # in interpret mode, so the numeric check runs a scaled vocab
        # slice; the bytes model is full-shape.
        d_chk, m_chk = 4096, 1024
        spec_chk = BloomSpec(d=d_chk, m=m_chk, k=k)
        H_chk = ops.cached_hash_matrix(spec_chk)
        logp_chk = jax.nn.log_softmax(jax.random.normal(key, (B, m_chk)))
        cot = jax.random.normal(key, (B, d_chk))
        g_pal = jax.grad(lambda lp: jnp.sum(
            bloom_decode_pallas(lp, H_chk, interpret=True) * cot))(logp_chk)
        g_ref = jax.grad(lambda lp: jnp.sum(
            ref.bloom_decode_ref(lp, H_chk) * cot))(logp_chk)
        bytes_dec_bwd = n_mtiles * (B * d * IS_F32 + d * k * its_idx) \
            + B * m * IS_F32
        bytes_dec_bwd_ideal = B * d * IS_F32 + d * k * its_idx \
            + B * m * IS_F32
        rows.append(_row(f"{name}.decode.bwd", B, bytes_dec_bwd,
                         _max_err(g_pal, g_ref),
                         bytes_ideal=bytes_dec_bwd_ideal,
                         bwd_bytes_ratio=round(bytes_dec_bwd
                                               / bytes_dec_bwd_ideal, 4),
                         check_d=d_chk, check_m=m_chk))

        # ---- decode bwd CSR: the shared row-scatter kernel on the
        # transposed cotangent, with H's bins cached per spec
        # (core.bloom.cached_decode_bins — binning amortizes to zero and
        # is NOT in the per-step model).  Same skew story as embed: the
        # bytes model is distribution-independent, the .skew row pins
        # numerics with the whole scaled vocab hashed into one m-tile.
        bytes_dec_bwd_csr = modeled_decode_bwd_csr_bytes(B, d, k, m)
        dc_chk, mc_chk = 2048, 1024     # nM=2 at check scale: the skew
        #                                 draw leaves m-tile 1 fully empty
        logp_c = jax.nn.log_softmax(
            jax.random.normal(jax.random.fold_in(key, 13), (B, mc_chk)))
        cot_c = jax.random.normal(jax.random.fold_in(key, 14), (B, dc_chk))
        for variant, hi in (("", mc_chk), (".skew", min(M_TILE, mc_chk))):
            H_c = jax.random.randint(jax.random.fold_in(key, 15),
                                     (dc_chk, k), 0, hi)
            g_pal = jax.grad(lambda lp: jnp.sum(
                bloom_decode_pallas(lp, H_c, interpret=True,
                                    bwd_impl="csr") * cot_c))(logp_c)
            g_ref = jax.grad(lambda lp: jnp.sum(
                ref.bloom_decode_ref(lp, H_c) * cot_c))(logp_c)
            rows.append(_row(
                f"{name}.decode.bwd.csr{variant}", B, bytes_dec_bwd_csr,
                _max_err(g_pal, g_ref),
                bytes_ideal=bytes_dec_bwd_ideal,
                bwd_bytes_ratio=round(bytes_dec_bwd_csr
                                      / bytes_dec_bwd_ideal, 4),
                vs_dense_ratio=round(bytes_dec_bwd
                                     / bytes_dec_bwd_csr, 4),
                skew="collision_heavy" if variant else "uniform",
                check_d=dc_chk, check_m=mc_chk))

        # ---- serving: decode-then-top_k vs fused decode_topk -------------
        # baseline writes the (B, d) score matrix to HBM and reads it back
        # for jax.lax.top_k
        want_v, _ = jax.lax.top_k(want_scores, TOPK)
        bytes_then = B * m * IS_F32 + d * k * its_idx \
            + 2 * B * d * IS_F32 + B * TOPK * IS_TOPK_PAIR
        base_v, _ = jax.lax.top_k(scores, TOPK)
        rows.append(_row(f"{name}.decode_then_topk", B, bytes_then,
                         _max_err(base_v, want_v), topk=TOPK))

        # fused kernel streams vocab tiles; running top-k stays in VMEM
        vals, ids = bloom_decode_topk_pallas(logp, H, TOPK)
        picked = jnp.take_along_axis(want_scores, ids, axis=-1)
        err = max(_max_err(vals, want_v), _max_err(picked, want_v))
        bytes_fused = B * m * IS_F32 + d * k * its_idx \
            + B * TOPK * IS_TOPK_PAIR
        row = _row(f"{name}.decode_topk", B, bytes_fused, err,
                   topk=TOPK, hbm_ratio=bytes_then / bytes_fused)
        rows.append(row)

        # ---- quantized fused decode-topk (DESIGN.md §13): the logp pool
        # is stored narrow AND the kernel re-derives the hash indices
        # in-graph (hash_spec, bit-identical to cached_hash_matrix) — the
        # (d, k) H stream, the dominant term at production d, disappears
        # entirely.  int8 adds one f32 scale per pool row, riding the
        # occupancy prefetch path.  Numeric check runs at the production
        # (B, m) like the legacy fused row, against the XLA oracle on the
        # FAKE-QUANTIZED logp (the models/io.py storage contract); int8
        # ids can legitimately flip on quantization-induced score ties
        # (XLA's FMA fusion differs per tile shape by 1 ulp), so the err
        # also scores the RETURNED ids through the oracle's score vector
        # (``picked``) — a flipped tie contributes 0 error, a wrong id
        # does not.
        for td, alias in QUANT_TOPK_SWEEP:
            q, s = quant.quantize_table(logp, td)
            want_q = ref.bloom_decode_ref(quant.dequantize_table(q, s), H)
            want_qv, _ = jax.lax.top_k(want_q, TOPK)
            vals_q, ids_q = bloom_decode_topk_pallas(
                logp, None, TOPK, table_dtype=td,
                hash_spec=(d, k, spec.seed))
            picked = jnp.take_along_axis(want_q, ids_q, axis=-1)
            err = max(_max_err(vals_q, want_qv), _max_err(picked, want_qv))
            bytes_q = modeled_hbm_bytes(
                np.ones(B, bool), B, m=m, d=d, k=k, topk=TOPK,
                logp_itemsize=quant.table_itemsize(td),
                inkernel_hash=True, row_scales=(td == "int8"))
            extra = {"vs_fp32_ratio": round(bytes_fused / bytes_q, 4)}
            if td == "int8":
                bytes_bf16 = modeled_hbm_bytes(
                    np.ones(B, bool), B, m=m, d=d, k=k, topk=TOPK,
                    logp_itemsize=quant.table_itemsize("bfloat16"),
                    inkernel_hash=True)
                extra["vs_bf16_ratio"] = round(bytes_bf16 / bytes_q, 4)
            row = _row(f"{name}.decode_topk.{alias}", B, bytes_q, err,
                       topk=TOPK, table_dtype=td, inkernel_hash=True,
                       **extra)
            rows.append(row)

        # ---- serving pool: row-skipping decode-topk vs slot occupancy ----
        # At pool size (B_POOL slots, b_tile row blocks) the grid streams
        # (b_tile*m logp + d*k H) bytes per VISITED row block — H is
        # re-streamed once per block because the vocab axis is innermost.
        # The dense grid visits all nB blocks regardless of occupancy; the
        # occupancy-prefetched grid (DESIGN.md §8) visits only the nA
        # blocks holding a live slot, so modeled HBM bytes scale with
        # active slots.  CI gates hbm_ratio_vs_full >= MIN_OCC_RATIO at
        # <= 50% occupancy.  Numeric check runs the skip grid against the
        # dense grid at a clamped (d, m) — interpret mode executes the
        # grid in Python — recorded in check_* fields.
        nB = B_POOL // BT_POOL
        d_chk, m_chk = 4096, 512
        spec_occ = BloomSpec(d=d_chk, m=m_chk, k=k)
        H_occ = ops.cached_hash_matrix(spec_occ)
        logp_occ = jax.nn.log_softmax(
            jax.random.normal(key, (B_POOL, m_chk)))
        dense_v, dense_i = bloom_decode_topk_pallas(
            logp_occ, H_occ, TOPK, b_tile=BT_POOL, v_tile=512,
            interpret=True)
        # the bytes model is single-sourced from the kernel module so it
        # can never drift from the grid it describes
        bytes_full = modeled_hbm_bytes(np.ones(B_POOL, bool), BT_POOL,
                                       m=m, d=d, k=k, topk=TOPK)
        for occ_name, frac in (("occ100", 1.0), ("occ50", 0.5),
                               ("occ12", 0.125)):
            n_act = int(B_POOL * frac)
            active = np.arange(B_POOL) < n_act
            nA = -(-n_act // BT_POOL)       # blocks holding a live slot
            bytes_occ = modeled_hbm_bytes(active, BT_POOL, m=m, d=d, k=k,
                                          topk=TOPK)
            vals_s, ids_s = bloom_decode_topk_pallas(
                logp_occ, H_occ, TOPK, b_tile=BT_POOL, v_tile=512,
                interpret=True, active=jnp.asarray(active))
            live = np.repeat(active.reshape(nB, BT_POOL).any(axis=1),
                             BT_POOL)
            err = max(_max_err(vals_s[live], dense_v[live]),
                      float(jnp.abs(ids_s[live]
                                    - dense_i[live]).max()))
            if not live.all():
                dead_ok = bool((np.asarray(vals_s)[~live]
                                == -np.inf).all()
                               and (np.asarray(ids_s)[~live] == 0).all())
                if not dead_ok:      # skipped rows must read (-inf, 0)
                    err = float("inf")
            rows.append(_row(
                f"{name}.decode_topk.{occ_name}", B_POOL, bytes_occ, err,
                topk=TOPK, occupancy=frac, active_slots=n_act,
                visited_blocks=nA, total_blocks=nB,
                hbm_ratio_vs_full=round(bytes_full / bytes_occ, 4),
                check_d=d_chk, check_m=m_chk))

        # ---- serving pool compaction: scattered vs densified occupancy
        # 4 host shards x SPH_POOL slots, 8 live per host on even local
        # slots: EVERY b_tile row block holds a live slot, so the
        # row-skipping grid recovers nothing (the b_tile-bound loss).
        # plan_compaction — the SAME planner the serving control plane
        # runs — packs each host's live slots into its dense prefix;
        # visited blocks halve and the compacted model lands exactly on
        # the globally-dense model.  CI gates >= MIN_OCC_RATIO recovery
        # and <= MAX_COMPACT_VS_DENSE of dense (ISSUE 4 acceptance).
        scattered = np.zeros(B_POOL, bool)
        scattered[::2] = True                      # 50% live, all blocks
        occupant = [s if scattered[s] else -1 for s in range(B_POOL)]
        perm = np.asarray(
            plan_compaction(occupant, SPH_POOL, threshold=0.0), np.int32)
        compacted = scattered[perm]
        dense = np.arange(B_POOL) < int(scattered.sum())
        b_sc = modeled_hbm_bytes(scattered, BT_POOL, m=m, d=d, k=k,
                                 topk=TOPK)
        b_co = modeled_hbm_bytes(compacted, BT_POOL, m=m, d=d, k=k,
                                 topk=TOPK)
        b_de = modeled_hbm_bytes(dense, BT_POOL, m=m, d=d, k=k, topk=TOPK)
        # numeric: the permuted pool recovers the SAME top-k per live
        # slot — compaction is a pure row move
        v_sc, i_sc = bloom_decode_topk_pallas(
            logp_occ, H_occ, TOPK, b_tile=BT_POOL, v_tile=512,
            interpret=True, active=jnp.asarray(scattered))
        v_co, i_co = bloom_decode_topk_pallas(
            logp_occ[perm], H_occ, TOPK, b_tile=BT_POOL, v_tile=512,
            interpret=True, active=jnp.asarray(compacted))
        live_new = np.flatnonzero(compacted)
        err = max(_max_err(v_co[live_new], v_sc[perm[live_new]]),
                  float(jnp.abs(i_co[live_new]
                                - i_sc[perm[live_new]]).max()))
        rows.append(_row(
            f"{name}.decode_topk.scatter_compact", B_POOL, b_co, err,
            topk=TOPK, occupancy=0.5,
            active_slots=int(scattered.sum()),
            slots_per_host=SPH_POOL,
            bytes_scattered=b_sc, bytes_dense=b_de,
            hbm_ratio_vs_scattered=round(b_sc / b_co, 4),
            vs_dense_ratio=round(b_co / b_de, 4),
            check_d=d_chk, check_m=m_chk))
    return rows


def write_json(rows, path=JSON_PATH, quick=True):
    """Write the committed bytes-model snapshot.

    Only --quick rows are accepted as the CI baseline: bytes models are
    production-shape either way, but check_* shapes (and thus max_err)
    depend on quick, and CI runs --quick --check — a full-run baseline
    would compare mismatched check shapes.
    """
    if not quick:
        raise ValueError("the committed baseline is generated with --quick "
                         "only; rerun with quick=True")
    payload = {
        "generated_by": "PYTHONPATH=src python -m benchmarks.bench_kernels"
                        " --quick",
        "hbm_bw_bytes_per_s": HBM_BW,
        "rows": rows,
    }
    path.write_text(json.dumps(payload, indent=1, sort_keys=True) + "\n")
    return path


def check_against(rows, path=JSON_PATH, err_slack=1e-3,
                  min_topk_ratio=3.0) -> list[str]:
    """Compare fresh rows to the committed JSON; return failure messages."""
    committed = {r["name"]: r for r in
                 json.loads(path.read_text())["rows"]}
    failures = []
    fresh_names = {r["name"] for r in rows}
    for gone in sorted(set(committed) - fresh_names):
        failures.append(f"{gone}: bench row disappeared from the fresh run "
                        "— a kernel bench was dropped or renamed")
    for r in rows:
        old = committed.get(r["name"])
        if old is None:
            failures.append(f"{r['name']}: missing from {path.name} — "
                            "regenerate with --quick")
            continue
        if r["max_err"] > old["max_err"] + err_slack:
            failures.append(
                f"{r['name']}: max_err regressed "
                f"{old['max_err']:.2e} -> {r['max_err']:.2e}")
        # the bytes model is deterministic given the production shapes —
        # any drift means kernel tiling and model went out of sync and
        # the baseline must be regenerated deliberately
        if r["bytes"] != old["bytes"]:
            failures.append(
                f"{r['name']}: bytes model changed "
                f"{old['bytes']} -> {r['bytes']}")
        if r["name"].endswith(".decode_topk") \
                and r.get("hbm_ratio", 0.0) < min_topk_ratio:
            failures.append(
                f"{r['name']}: fused top-k HBM ratio {r['hbm_ratio']:.2f} "
                f"< {min_topk_ratio} — serving fusion no longer pays")
        # quantized-table acceptance bars (ISSUE 9, DESIGN.md §13): the
        # int8 rows must model >= MIN_INT8_VS_FP32 fewer total bytes
        # than their fp32 twin (embed.fwd.fp32 / the legacy f32
        # decode_topk row) and >= MIN_INT8_VS_BF16 fewer than bf16
        # (table stream for embed, whole row for decode-topk); the fp8
        # rows ride the same drift check via bytes equality above
        if r["name"].endswith(".embed.fwd.int8") \
                or r["name"].endswith(".decode_topk.int8"):
            if r.get("vs_fp32_ratio", 0.0) < MIN_INT8_VS_FP32:
                failures.append(
                    f"{r['name']}: int8/fp32 bytes ratio "
                    f"{r.get('vs_fp32_ratio', 0.0):.2f} < "
                    f"{MIN_INT8_VS_FP32} — int8 storage no longer closes "
                    "the table-stream gap")
            if r.get("vs_bf16_ratio", 0.0) < MIN_INT8_VS_BF16:
                failures.append(
                    f"{r['name']}: int8/bf16 bytes ratio "
                    f"{r.get('vs_bf16_ratio', 0.0):.2f} < "
                    f"{MIN_INT8_VS_BF16} — int8 no longer beats plain "
                    "bf16 storage meaningfully")
        # CSR-binned backward acceptance bars (ISSUE 5): the binned
        # scatter-add must model >= MIN_*_CSR_RATIO fewer HBM bytes than
        # the dense m-tile sweep at the production shape, on the uniform
        # AND the collision-heavy (skew) rows alike — the model is
        # distribution-independent, so a diverging skew row means the
        # kernel/model went out of sync
        if ".embed.bwd.csr" in r["name"] \
                and r.get("vs_dense_ratio", 0.0) < MIN_EMBED_CSR_RATIO:
            failures.append(
                f"{r['name']}: CSR/dense bytes ratio "
                f"{r.get('vs_dense_ratio', 0.0):.2f} < "
                f"{MIN_EMBED_CSR_RATIO} — the binned embed backward no "
                "longer closes the backward bytes gap")
        if ".decode.bwd.csr" in r["name"] \
                and r.get("vs_dense_ratio", 0.0) < MIN_DECODE_CSR_RATIO:
            failures.append(
                f"{r['name']}: CSR/dense bytes ratio "
                f"{r.get('vs_dense_ratio', 0.0):.2f} < "
                f"{MIN_DECODE_CSR_RATIO} — the binned decode backward "
                "no longer closes the backward bytes gap")
        # row-skipping acceptance bar (ISSUE 3): at <= 50% slot occupancy
        # the occupancy grid must model >= MIN_OCC_RATIO fewer HBM bytes
        # than the full pool
        if (".decode_topk.occ" in r["name"]
                and not r["name"].endswith(".occ100")
                and r.get("occupancy", 1.0) <= 0.5
                and r.get("hbm_ratio_vs_full", 0.0) < MIN_OCC_RATIO):
            failures.append(
                f"{r['name']}: occupancy bytes ratio "
                f"{r.get('hbm_ratio_vs_full', 0.0):.2f} < {MIN_OCC_RATIO} "
                "— row skipping no longer pays at partial occupancy")
        # compaction acceptance bar (ISSUE 4): densifying a scattered
        # pool must recover >= MIN_OCC_RATIO of the modeled bytes AND
        # land within MAX_COMPACT_VS_DENSE of the globally-dense model
        if r["name"].endswith(".decode_topk.scatter_compact"):
            if r.get("hbm_ratio_vs_scattered", 0.0) < MIN_OCC_RATIO:
                failures.append(
                    f"{r['name']}: compaction bytes recovery "
                    f"{r.get('hbm_ratio_vs_scattered', 0.0):.2f} < "
                    f"{MIN_OCC_RATIO} — densifying scattered slots no "
                    "longer pays")
            if r.get("vs_dense_ratio", float("inf")) \
                    > MAX_COMPACT_VS_DENSE:
                failures.append(
                    f"{r['name']}: compacted bytes are "
                    f"{r.get('vs_dense_ratio', float('inf')):.2f}x the "
                    f"dense-occupancy model (> {MAX_COMPACT_VS_DENSE}) — "
                    "per-host packing is leaving b_tile tails behind")
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--check", action="store_true",
                    help="compare against committed BENCH_kernels.json and "
                         "fail on max_err / hbm_ratio regressions")
    args = ap.parse_args()
    if args.check and not args.quick:
        # the committed baseline records --quick check shapes; comparing
        # full-run max_err against it would validate mismatched shapes
        ap.error("--check requires --quick (the baseline is "
                 "--quick-generated)")
    rows = run(quick=args.quick)
    for row in rows:
        print(row)
    if args.check:
        failures = check_against(rows)
        for f in failures:
            print("REGRESSION:", f, file=sys.stderr)
        if failures:
            sys.exit(1)
        print(f"check ok: {len(rows)} rows vs {JSON_PATH.name}")
    elif args.quick:
        print("wrote", write_json(rows, quick=True))
    else:
        print(f"not writing {JSON_PATH.name}: the committed baseline is "
              "--quick-generated; rerun with --quick")


if __name__ == "__main__":
    main()
