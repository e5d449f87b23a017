"""64-bit arithmetic on (hi, lo) uint32 limb pairs, in jax.numpy.

The reference runs on the device after the measured window, where JAX
has no 64-bit integers unless x64 is switched on for the whole process.
So 64-bit values travel as two uint32 arrays, and numpy (which has
uint64) prepares the per-row and per-column operands on the host.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

U32 = jnp.uint32
M16 = np.uint32(0xFFFF)


def split(x: np.ndarray):
    """uint64 numpy array -> (hi, lo) uint32 numpy arrays."""
    x = np.asarray(x, np.uint64)
    return ((x >> np.uint64(32)).astype(np.uint32),
            (x & np.uint64(0xFFFFFFFF)).astype(np.uint32))


def const(v: int):
    v &= (1 << 64) - 1
    return np.uint32(v >> 32), np.uint32(v & 0xFFFFFFFF)


def add(a, b):
    lo = a[1] + b[1]
    return a[0] + b[0] + (lo < a[1]).astype(U32), lo


def xor(a, b):
    return a[0] ^ b[0], a[1] ^ b[1]


def shr(a, n: int):
    hi, lo = a
    if n < 32:
        return hi >> n, (lo >> n) | (hi << (32 - n))
    return jnp.zeros_like(hi), hi >> (n - 32)


def _mul32(a, b):
    """Full 32x32 -> 64-bit product from 16-bit halves."""
    al, ah = a & M16, a >> 16
    bl, bh = b & M16, b >> 16
    ll, lh, hl, hh = al * bl, al * bh, ah * bl, ah * bh
    mid = (ll >> 16) + (lh & M16) + (hl & M16)
    return (hh + (lh >> 16) + (hl >> 16) + (mid >> 16),
            (ll & M16) | ((mid & M16) << 16))


def mul(a, b):
    """(a * b) mod 2**64."""
    hi, lo = _mul32(a[1], b[1])
    return hi + a[1] * b[0] + a[0] * b[1], lo


def ror32(x, r):
    r = r & U32(31)
    return (x >> r) | (x << ((U32(32) - r) & U32(31)))
