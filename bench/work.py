"""Operations and bytes the algorithms need, from their shapes alone.

These counts are the benchmark's yardstick, independent of how the
program implements the work: a kernel that hashes in-kernel, skips rows
or fuses passes does the same algorithmic work and is judged against the
same numbers.  Hash indices are derivable from the seed, so reading them
is never counted as necessary traffic.
"""
from __future__ import annotations


def decode_topk(rows: int, *, d: int, m: int, k: int, topk: int
                ) -> tuple[float, float]:
    """Eq. 3 recovery with a streaming top-k over ``rows`` pool rows:
    (operations, bytes).  Operations: the ``d*k`` score additions and one
    compare of each item against the running top-k.  Bytes: the (rows, m)
    f32 log-probability rows read and the (rows, topk) f32 scores plus
    int32 ids written."""
    ops = rows * (d * k + d)
    nbytes = rows * (m * 4 + topk * 8)
    return float(ops), float(nbytes)


def retrieval_query_flops(*, d: int, m: int, k: int, hidden) -> float:
    """Model FLOPs of one retrieval query: the FF tower's matmuls from the
    m-dim Bloom code to the m-dim logits, and the Eq. 3 additions over
    the whole catalog."""
    dims = [m, *hidden, m]
    tower = sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    return float(tower + d * k)


def _lm_dims(cfg: dict):
    D, H, KV = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or D // H
    return D, H, KV, hd


def lm_matmul_params(cfg: dict) -> int:
    """Weights that every token multiplies through: attention and MLP of
    every layer and the m-dim tied output head (the Bloom-embedding gather
    on the input side is k row additions, not a matmul)."""
    D, H, KV, hd = _lm_dims(cfg)
    per_layer = (D * H * hd + 2 * D * KV * hd + H * hd * D
                 + 3 * D * cfg["intermediate_size"])
    return cfg["num_hidden_layers"] * per_layer + D * cfg["bloom_m"]


def lm_attention_flops(cfg: dict, keys: float) -> float:
    """QK^T and PV FLOPs of one query position attending ``keys`` keys,
    over all layers."""
    D, H, KV, hd = _lm_dims(cfg)
    return 4.0 * keys * H * hd * cfg["num_hidden_layers"]


def lm_recovery_ops(cfg: dict) -> float:
    """Eq. 3 additions of one recovered position over the whole vocab."""
    return float(cfg["vocab_size"] * cfg["bloom_k"])


def lm_prefill_flops(cfg: dict, length: int) -> float:
    """Forward of one causal prompt of ``length`` tokens plus the Eq. 3
    recovery of its last position."""
    tri = length * (length + 1) / 2
    return (2.0 * lm_matmul_params(cfg) * length
            + lm_attention_flops(cfg, tri) + lm_recovery_ops(cfg))


def lm_prefill_flops_sum(cfg: dict, n: int, tokens: int, tokens_sq: int
                         ) -> float:
    """``lm_prefill_flops`` summed over ``n`` prompts whose lengths sum
    to ``tokens`` and whose squares sum to ``tokens_sq``."""
    tri = (tokens_sq + tokens) / 2
    return (2.0 * lm_matmul_params(cfg) * tokens
            + lm_attention_flops(cfg, tri) + n * lm_recovery_ops(cfg))


def lm_decode_flops(cfg: dict, rows: int, keys: float) -> float:
    """One decode step of ``rows`` live slots that attend ``keys`` cached
    positions in total, each with its Eq. 3 recovery."""
    return (rows * (2.0 * lm_matmul_params(cfg) + lm_recovery_ops(cfg))
            + lm_attention_flops(cfg, keys))
