"""Plain float32 reference of Bloom retrieval (paper Sec. 3.2, Eqs. 1
and 3): encode the item set as a k-hot m-vector, run the feed-forward
tower (ReLU between layers), log-softmax, and score every catalog item
by the sum of its k log-probabilities.  Every matmul runs at
``precision="highest"``; the catalog is scored in blocks so that no
(rows, d) matrix is ever held."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference import bloom_hash


def encode(items, *, m: int, k: int, seed: int):
    """items (S, c) int, -1 padded -> (S, m) float32 0/1."""
    valid = items >= 0
    idx = bloom_hash.indices(jnp.where(valid, items, 0), k=k, m=m,
                             seed=seed)                      # (S, c, k)
    hot = jax.nn.one_hot(idx, m, dtype=jnp.float32)          # (S, c, k, m)
    hot = hot * valid[..., None, None]
    return jnp.max(hot.reshape(items.shape[0], -1, m), axis=1)


def log_probs(tower: list, items, *, m: int, k: int, seed: int):
    """tower: [(w, b)] float32; items (S, c) -> (S, m) log-probs."""
    with jax.default_matmul_precision("highest"):
        h = encode(items, m=m, k=k, seed=seed)
        for i, (w, b) in enumerate(tower):
            h = h @ w + b
            if i < len(tower) - 1:
                h = jax.nn.relu(h)
        return jax.nn.log_softmax(h, axis=-1)


def scores(logp, ids, *, m: int, k: int, seed: int):
    """logp (S, m); ids (n,) -> (S, n) Eq. 3 scores sum_j logp[h_j(id)]."""
    idx = bloom_hash.indices(ids, k=k, m=m, seed=seed)       # (n, k)
    rows = jnp.take(logp.T, idx, axis=0)                     # (n, k, S)
    return rows.sum(axis=1).T


def scores_of(logp, ids, *, m: int, k: int, seed: int):
    """logp (S, m); ids (S, t) -> (S, t): each row's own items' scores."""
    idx = bloom_hash.indices(ids, k=k, m=m, seed=seed)       # (S, t, k)
    return jnp.take_along_axis(logp[:, None, :],
                               idx.reshape(ids.shape[0], 1, -1),
                               axis=-1).reshape(idx.shape).sum(-1)


@functools.partial(jax.jit, static_argnames=("m", "k", "seed", "topk",
                                             "block"))
def _block_topk(logp, best_v, start, *, m, k, seed, topk, block):
    ids = start + jnp.arange(block, dtype=jnp.int32)
    s = scores(logp, ids, m=m, k=k, seed=seed)
    return jax.lax.top_k(jnp.concatenate([best_v, s], axis=1), topk)[0]


def topk_values(logp, *, d: int, m: int, k: int, seed: int, topk: int,
                block: int = 1 << 20):
    """The ``topk`` best Eq. 3 scores over the whole catalog, per row,
    descending.  Ids past ``d`` in the last block are scored as -inf."""
    S = logp.shape[0]
    best = jnp.full((S, topk), -jnp.inf, jnp.float32)
    n_full = d // block
    for b in range(n_full):
        best = _block_topk(logp, best, jnp.int32(b * block), m=m, k=k,
                           seed=seed, topk=topk, block=block)
    tail = d - n_full * block
    if tail:
        ids = n_full * block + jnp.arange(tail, dtype=jnp.int32)
        s = scores(logp, ids, m=m, k=k, seed=seed)
        best = jax.lax.top_k(jnp.concatenate([best, s], axis=1), topk)[0]
    return best
