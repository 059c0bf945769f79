"""Operations and bytes of one decode step of the Bloom-embedded LM, from
its shapes alone, for the step's roofline share.

The counts are the algorithm's, not the program's: a step of ``live``
rows that attend ``keys`` cached positions in all reads every matmul
weight once, the keys and values of those positions, and the live rows'
m-dim log-probabilities for the Eq. 3 recovery.  The pool's padding to
``max_len`` and its idle rows are not work.
"""
from __future__ import annotations

import jax.numpy as jnp

from bench import work


def decode_step_bytes(cfg: dict, live: int, keys: float, topk: int
                      ) -> float:
    """HBM bytes one decode step needs: the matmul weights
    (``work.lm_matmul_params``) at the compute dtype, read once for all
    rows; the cached keys and values of the ``keys`` positions attended,
    over every layer; and ``work.decode_topk``'s bytes for the ``live``
    rows' recovery over the whole vocabulary."""
    size = jnp.dtype(cfg["compute_dtype"]).itemsize
    _, _, kv_heads, head_dim = work._lm_dims(cfg)
    weights = work.lm_matmul_params(cfg) * size
    kv = keys * cfg["num_hidden_layers"] * 2 * kv_heads * head_dim * size
    _, recover = work.decode_topk(live, d=cfg["vocab_size"],
                                  m=cfg["bloom_m"], k=cfg["bloom_k"],
                                  topk=topk)
    return float(weights + kv + recover)


def decode_step(cfg: dict, live: int, keys: float, topk: int
                ) -> tuple[float, float]:
    """(operations, bytes) of one decode step: ``work.lm_decode_flops``
    and ``decode_step_bytes``."""
    return (work.lm_decode_flops(cfg, live, keys),
            decode_step_bytes(cfg, live, keys, topk))
