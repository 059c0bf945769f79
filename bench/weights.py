"""Weights from the seed, made on the device in one jitted call.

The benchmark, not the program, makes the weights: the plain references
rebuild the very same arrays from the seed and take nothing the program
made.  Only the tree's structure (leaf names and shapes) is the
program's.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any whole number: the low 32 bits seed the key,
    the rest is folded in."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def _leaf(key, path: str, shape, fan_in: int | None, kind: str):
    if kind == "scale":
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    if kind == "bias":
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    if kind == "embed":
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    std = 1.0 / math.sqrt(fan_in)
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                             jnp.float32)


def generate(key, leaves: dict, cast=None) -> dict:
    """``leaves``: {path: (shape, fan_in, kind)}.  Returns {path: array},
    each leaf from its own fold of ``key`` (by its position in sorted
    path order), cast by ``cast(path, array)`` where given.  Traceable."""
    out = {}
    for i, p in enumerate(sorted(leaves)):
        shape, fan_in, kind = leaves[p]
        a = _leaf(jax.random.fold_in(key, i), p, shape, fan_in, kind)
        out[p] = cast(p, a) if cast is not None else a
    return out


def make(seed: int, leaves: dict, cast=None) -> dict:
    """``generate`` from the seed, in one jitted call on the device."""
    return jax.jit(lambda key: generate(key, leaves, cast))(seed_key(seed))
