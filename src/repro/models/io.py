"""Token IO boundary: embedding + LM head, dense or Bloom-compressed.

This is where the paper's technique plugs into every architecture
(DESIGN.md §5): with bloom.enabled the embedding table and LM head operate
in the m-dim hashed space; the per-token loss and serving-time vocabulary
recovery use the k-way likelihood of Eqs. 2/3.

io_impl selects the execution path:
  "xla"    — pure jnp (gather/take); the oracle, and the dry-run path.
  "pallas" — fused TPU kernels from repro.kernels (validated vs this file).

On the pallas path, bwd_impl selects the training backward of the Bloom
scatter-adds: "csr" (default — CSR-binned segment kernel, reads the
cotangent ~k times total, DESIGN.md §4) or "dense" (the m-tile-sweep
fallback).  Both match the xla oracle's jax.grad to <= 1e-4.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import losses, quant
from repro.core.bloom import BloomSpec, decode_topk
from repro.models import layers


def resolved_table_dtype(cfg: ModelConfig) -> Optional[str]:
    """ModelConfig.table_dtype -> kernel-layer knob (DESIGN.md §13).

    The config default "auto" maps to ``None`` (legacy behavior: cast the
    table to the activation dtype, no quantization) so pre-quant configs
    stay bit-identical; anything else is canonicalized by core.quant.
    """
    td = quant.resolve_table_dtype(cfg.table_dtype, allow_auto=True)
    return None if td == "auto" else td


def _fake_quant_rows(x: jnp.ndarray, table_dtype: str) -> jnp.ndarray:
    """Quantize+dequantize (..., m) rows — the XLA oracle's storage model.

    The xla io_impl has no narrow HBM tables, but it must RANK through the
    same dequantized values the Pallas kernels see, or accuracy sweeps
    (bench_retrieval.py int8 retention) would silently compare a quantized
    kernel against an unquantized oracle.  Row axis = last axis, matching
    the per-row scales of core.quant.
    """
    flat = x.reshape(-1, x.shape[-1])
    q, s = quant.quantize_table(flat, table_dtype)
    return quant.dequantize_table(q, s).reshape(x.shape)


def vocab_spec(cfg: ModelConfig) -> Optional[BloomSpec]:
    if not cfg.bloom.enabled:
        return None
    return BloomSpec(d=cfg.vocab, m=cfg.m_vocab, k=cfg.bloom.k,
                     seed=cfg.bloom.seed, on_the_fly=cfg.bloom.on_the_fly)


def io_init(key, cfg: ModelConfig):
    V, D = cfg.m_vocab, cfg.d_model
    k1, k2 = jax.random.split(key)
    p = {"embed": layers.embed_init(k1, (V, D))}
    if not cfg.tie_embeddings:
        p["head"] = layers.truncated_normal_init(k2, (D, V), 1.0)
    return p


def embed_tokens(params, cfg: ModelConfig, tokens: jnp.ndarray,
                 ) -> jnp.ndarray:
    """tokens (B, S) int32 -> (B, S, D) activations.

    Bloom path: x = sum_j Table[H_j(tok)] — the dense-matrix product with
    the k-hot Bloom code of the paper, computed as a k-way gather-sum.
    """
    table = params["embed"]
    dt = jnp.dtype(cfg.dtype)
    spec = vocab_spec(cfg)
    if spec is None:
        return jnp.take(table, tokens, axis=0).astype(dt)
    td = resolved_table_dtype(cfg)
    if cfg.io_impl == "pallas":
        from repro.kernels import ops
        if td is None:
            return ops.bloom_embed(table.astype(dt), tokens, spec,
                                   bwd_impl=cfg.bwd_impl)
        # master-precision table in; the kernel stores/DMAs it narrow and
        # dequantizes on the VMEM tile (grads straight-through to master)
        return ops.bloom_embed(table, tokens, spec, bwd_impl=cfg.bwd_impl,
                               table_dtype=td, out_dtype=dt)
    if td is not None:
        table = _fake_quant_rows(table, td)
    idx = spec.indices_for(tokens)                     # (B, S, k)
    rows = jnp.take(table, idx, axis=0).astype(dt)     # (B, S, k, D)
    return rows.sum(axis=2)


def lm_logits(params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """x (B, S, D) -> logits (B, S, m_vocab) (m-dim when bloom enabled)."""
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    return x @ head.astype(x.dtype)


def lm_loss(params, cfg: ModelConfig, logits: jnp.ndarray,
            labels: jnp.ndarray, valid: Optional[jnp.ndarray] = None
            ) -> jnp.ndarray:
    """Per-token CE. Bloom: logsumexp(z) - (1/k) sum_j z[H_j(y)] (Eq. 3)."""
    spec = vocab_spec(cfg)
    logits = logits.astype(jnp.float32)
    if spec is None:
        return losses.softmax_xent_label(logits, labels, valid)
    if cfg.io_impl == "pallas":
        from repro.kernels import ops
        loss = ops.bloom_ce(logits, labels, spec)
        return loss if valid is None else loss * valid.astype(loss.dtype)
    return losses.bloom_xent_label(spec, logits, labels, valid=valid)


def recover_topk(cfg: ModelConfig, logits: jnp.ndarray, topk: int = 16,
                 chunk: int = 8192, active: Optional[jnp.ndarray] = None):
    """Serving-time vocabulary recovery (paper Sec. 3.2).

    logits (..., m_vocab) -> (scores, token_ids) (..., topk) over the
    original vocab.  Dense path: plain top-k.  Bloom path: Eq. 3 scores
    via the streaming k-gather reduction; with io_impl="pallas" the fused
    decode-topk kernel keeps the running top-k in VMEM and never writes
    the (..., d) recovered-score matrix to HBM.

    `active` (..., ) bool marks live slots in a continuous-batching pool:
    retired/idle slots get ids=0 and scores=-inf so engine bookkeeping
    can never mistake a stale row for output.  With io_impl="pallas" the
    mask additionally drives the kernel's row-skipping occupancy grid
    (DESIGN.md §8): fully-inactive row blocks are skipped at the HBM
    level, so a half-empty pool no longer pays full-pool bytes; the
    post-hoc where() below still masks dead rows inside partially-live
    blocks.
    """
    spec = vocab_spec(cfg)
    return recover_topk_spec(spec, logits, topk, impl=cfg.io_impl,
                             chunk=chunk, active=active,
                             unroll=cfg.unroll_for_analysis,
                             table_dtype=resolved_table_dtype(cfg))


def recover_topk_spec(spec: Optional[BloomSpec], logits: jnp.ndarray,
                      topk: int = 16, *, impl: str = "xla",
                      chunk: int = 8192,
                      active: Optional[jnp.ndarray] = None,
                      unroll: bool = False,
                      table_dtype: Optional[str] = None):
    """``recover_topk`` keyed by a BloomSpec instead of a ModelConfig —
    the shared recovery core for the LM head AND the retrieval scenario
    (serving/retrieval.py), which has no ModelConfig to hand.

    All three paths follow the SAME tie-break contract (DESIGN.md §11):
    equal Eq. 3 scores resolve to the lowest item id, exactly like
    ``jax.lax.top_k`` on a materialized score vector — the streaming
    oracle seeds each chunk merge with the running best (earlier = lower
    ids first in the concat), and the Pallas kernel folds tiles in
    ascending vocab order, taking the lowest id among equal scores.

    ``table_dtype`` (DESIGN.md §13, None = legacy f32) narrows the
    resident logp rows: the Pallas kernel stores them narrow in HBM and
    dequantizes on the VMEM tile; the streaming oracle fake-quantizes the
    SAME per-row storage model before ranking, so a MAP measured on the
    xla path is an honest proxy for the quantized kernel.  (int8 ids may
    still differ by quantization-induced score ties — the scores agree
    to float rounding; see tests/test_kernels.py.)
    """
    if spec is None:
        scores, ids = jax.lax.top_k(logits, topk)
    else:
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        td = quant.resolve_table_dtype(table_dtype)
        if impl == "pallas":
            from repro.kernels import ops
            scores, ids = ops.bloom_decode_topk(logp, spec, topk,
                                                active=active,
                                                table_dtype=td)
        else:
            if td is not None:
                logp = _fake_quant_rows(logp, td)
            scores, ids = decode_topk(spec, logp, topk, chunk=chunk,
                                      unroll=unroll)
    if active is not None:
        live = active[..., None]
        scores = jnp.where(live, scores, -jnp.inf)
        ids = jnp.where(live, ids, 0)
    return scores, ids
