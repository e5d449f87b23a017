"""Plain reference of ThundeRiNG's counter-mode (ctr) MISRN generator.

Element (t, s) of the window that starts at counter ``lo`` is

    XSH_RR(root[lo + t + 1] + h_s)  ^  fold(splitmix64(h_s ^ K, lo + t))

where ``root[n]`` is the PCG64 LCG state after n steps from the family's
root ``x0``, ``h_s`` is stream s's even leaf offset, ``K`` the
decorrelator's key constant and ``fold(z) = hi32(z) ^ lo32(z)``.  The
family (x0, h) comes from the seed and the channel's purpose tag exactly
as the paper's root/leaf split prescribes (arXiv:2105.09578, Sec. 3.3).
"""
from __future__ import annotations

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import limbs

M64 = (1 << 64) - 1
LCG_A = 6364136223846793005          # PCG64 / MMIX multiplier
LCG_C = 1442695040888963407          # PCG64 reference increment
GAMMA = 0x9E3779B97F4A7C15
MIX1 = 0xBF58476D1CE4E5B9
MIX2 = 0x94D049BB133111EB
DECO_KEY = 0xD1B54A32D192ED03
ROOT_TAG = 0x1234


def mix64_host(z: int) -> int:
    z &= M64
    z ^= z >> 30
    z = (z * MIX1) & M64
    z ^= z >> 27
    z = (z * MIX2) & M64
    return z ^ (z >> 31)


def splitmix64_host(seed: int, index: int) -> int:
    return mix64_host(seed + (index + 1) * GAMMA)


def _mix64_np(z: np.ndarray) -> np.ndarray:
    z = z ^ (z >> np.uint64(30))
    z = z * np.uint64(MIX1)
    z = z ^ (z >> np.uint64(27))
    z = z * np.uint64(MIX2)
    return z ^ (z >> np.uint64(31))


def channel_purpose(name: str) -> int:
    """The 64-bit purpose tag of a named delivery channel."""
    return int.from_bytes(
        hashlib.blake2s(name.encode(), digest_size=8).digest(), "little")


def family(seed: int, purpose: int):
    """(x0, h_family) python ints of a seed's stream family."""
    x0 = splitmix64_host(seed & M64, ROOT_TAG)
    return x0, (splitmix64_host(seed, purpose) << 1) & M64


def leaf_offsets(h_family: int, cols: np.ndarray) -> np.ndarray:
    """uint64 even leaf offsets of streams ``cols``."""
    with np.errstate(over="ignore"):
        idx = np.asarray(cols, np.uint64) + np.uint64(1)
        z = np.uint64(h_family) + idx * np.uint64(GAMMA)
        return _mix64_np(z) << np.uint64(1)


def lcg_advance(x: int, n: int) -> int:
    """x after n LCG steps (Brown's jump-ahead)."""
    A, C, a, c = 1, 0, LCG_A, LCG_C
    while n:
        if n & 1:
            A, C = (A * a) & M64, (C * a + c) & M64
        a, c = (a * a) & M64, ((a + 1) * c) & M64
        n >>= 1
    return (A * x + C) & M64


def root_rows(x0: int, start: int, rows: int) -> np.ndarray:
    """uint64 root states after start+1 .. start+rows LCG steps."""
    out = np.empty(rows, np.uint64)
    x = lcg_advance(x0, start + 1)
    for i in range(rows):
        out[i] = x
        x = (LCG_A * x + LCG_C) & M64
    return out


def step_rows(start: int, rows: int) -> np.ndarray:
    """uint64 (counter + 1) * GAMMA for counters start .. start+rows-1."""
    with np.errstate(over="ignore"):
        ctr = np.uint64(start & M64) + np.arange(rows, dtype=np.uint64)
        return (ctr + np.uint64(1)) * np.uint64(GAMMA)


def xsh_rr(state):
    x = limbs.xor(limbs.shr(state, 18), state)
    return limbs.ror32(limbs.shr(x, 27)[1], state[0] >> 27)


def mix64(z):
    z = limbs.xor(z, limbs.shr(z, 30))
    z = limbs.mul(z, limbs.const(MIX1))
    z = limbs.xor(z, limbs.shr(z, 27))
    z = limbs.mul(z, limbs.const(MIX2))
    return limbs.xor(z, limbs.shr(z, 31))


def bits(root, step, h, hk):
    """(R, S) uint32 from per-row (root, step) and per-column (h, h ^ K)
    limb pairs: R-vectors against S-vectors."""
    col = lambda p: (p[0][None, :], p[1][None, :])  # noqa: E731
    row = lambda p: (p[0][:, None], p[1][:, None])  # noqa: E731
    perm = xsh_rr(limbs.add(row(root), col(h)))
    z = mix64(limbs.add(col(hk), row(step)))
    return perm ^ z[0] ^ z[1]


@functools.partial(jax.jit, static_argnames=("rows",))
def _mismatches(blk, r0, rows, root_hi, root_lo, step_hi, step_lo,
                h_hi, h_lo, hk_hi, hk_lo):
    got = jax.lax.dynamic_slice_in_dim(blk, r0, rows, axis=0)
    want = bits((root_hi, root_lo), (step_hi, step_lo), (h_hi, h_lo),
                (hk_hi, hk_lo))
    return jnp.sum((got != want).astype(jnp.int32))


class Stream:
    """The family of one channel: seed, purpose and the columns held."""

    def __init__(self, seed: int, purpose: int, cols: np.ndarray):
        self.x0, h_family = family(seed, purpose)
        self.h = leaf_offsets(h_family, cols)
        self.hk = self.h ^ np.uint64(DECO_KEY)

    def column_args(self, device=None):
        args = (*limbs.split(self.h), *limbs.split(self.hk))
        return tuple(jax.device_put(a, device) for a in args)

    def row_args(self, start: int, rows: int, device=None):
        args = (*limbs.split(root_rows(self.x0, start, rows)),
                *limbs.split(step_rows(start, rows)))
        return tuple(jax.device_put(a, device) for a in args)


def mismatches(blk, lo: int, stream: Stream, *, chunk: int = 256,
               device=None) -> int:
    """Elements of the (T, S) block ``blk`` (window at counter ``lo``,
    columns as in ``stream``) that differ from the reference."""
    T = blk.shape[0]
    cols = stream.column_args(device)
    total = 0
    for r0 in range(0, T, chunk):
        rows = min(chunk, T - r0)
        total += int(_mismatches(blk, r0, rows,
                                 *stream.row_args(lo + r0, rows, device),
                                 *cols))
    return total
