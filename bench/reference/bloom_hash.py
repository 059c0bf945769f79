"""Bloom hash indices, written from their definition (enhanced double
hashing, Dillinger & Manolios 2004, over a SplitMix32 mixer):

    a(x) = mix(x ^ mix(2s)) mod m
    b(x) = mix(x ^ mix(2s+1)) mod (m-1) + 1
    h_j(x) = (a(x) + j*b(x) + (j^3 - j)/6) mod m,   j = 0..k-1

with uint32 wrap-around arithmetic and ``mix`` the SplitMix32 finalizer.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

_M32 = 0xFFFFFFFF


def _mix_int(x: int) -> int:
    z = (x + 0x9E3779B9) & _M32
    z = ((z ^ (z >> 16)) * 0x85EBCA6B) & _M32
    z = ((z ^ (z >> 13)) * 0xC2B2AE35) & _M32
    return z ^ (z >> 16)


def _mix(x):
    z = x + np.uint32(0x9E3779B9)
    z = (z ^ (z >> 16)) * np.uint32(0x85EBCA6B)
    z = (z ^ (z >> 13)) * np.uint32(0xC2B2AE35)
    return z ^ (z >> 16)


def indices(ids, *, k: int, m: int, seed: int):
    """ids (...) int -> (..., k) int32 hash indices in [0, m)."""
    x = jnp.asarray(ids).astype(jnp.uint32)
    s1 = np.uint32(_mix_int((2 * seed) & _M32))
    s2 = np.uint32(_mix_int((2 * seed + 1) & _M32))
    a = _mix(x ^ s1) % np.uint32(m)
    b = _mix(x ^ s2) % np.uint32(max(m - 1, 1)) + np.uint32(1)
    out = []
    for j in range(k):
        tri = np.uint32(((j ** 3 - j) // 6) % m)
        out.append((a + np.uint32(j) * b + tri) % np.uint32(m))
    return jnp.stack(out, axis=-1).astype(jnp.int32)
