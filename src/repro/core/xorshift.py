"""xorshift128 decorrelator (Marsaglia 2003) with GF(2) jump-ahead.

ThundeRiNG (Sec. 3.2.3) decorrelates the LCG leaf streams by XORing each
with a *substream* of a single xorshift128 generator, substreams spaced
2**64 steps apart so any pair is guaranteed non-overlapping (Sec. 5.1.2).

xorshift128 is F2-linear: the 128-bit state advances by a fixed bit-matrix
``M`` over GF(2).  Jump-ahead by N steps is multiplication by ``M**N``.  We
compute ``M**(2**64)`` once at import (host-side python-int bit tricks —
the paper's "compile time", Sec. 4.2) and derive the i-th substream's start
state with i matrix-vector products (batched for lane tables).

State layout: (x, y, z, w) four uint32 words; output is the new ``w``.
Bit k of the flattened 128-bit state = bit (k % 32) of word (k // 32).
"""
from __future__ import annotations

import functools
from typing import List, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.u64 import U32

# Default seed from Marsaglia's paper.
DEFAULT_SEED = (123456789, 362436069, 521288629, 88675123)

STATE_WORDS = 4
STATE_BITS = 128


def step_words(x: int, y: int, z: int, w: int) -> Tuple[int, int, int, int]:
    """One xorshift128 step on python ints (host-side golden)."""
    t = (x ^ (x << 11)) & 0xFFFFFFFF
    x, y, z = y, z, w
    w = (w ^ (w >> 19)) ^ (t ^ (t >> 8))
    return x, y, z, w & 0xFFFFFFFF


def step(state: jnp.ndarray) -> jnp.ndarray:
    """One xorshift128 step; state shape (..., 4) uint32. Output = new w."""
    x = state[..., 0]
    y = state[..., 1]
    z = state[..., 2]
    w = state[..., 3]
    t = x ^ (x << U32(11))
    new_w = (w ^ (w >> U32(19))) ^ (t ^ (t >> U32(8)))
    return jnp.stack([y, z, w, new_w], axis=-1)


def step_xyzw(x, y, z, w):
    """One step on four separate uint32 arrays (Pallas-friendly, no stack)."""
    t = x ^ (x << U32(11))
    new_w = (w ^ (w >> U32(19))) ^ (t ^ (t >> U32(8)))
    return y, z, w, new_w


# ----------------------------------------------------------------------------
# GF(2) linear-algebra machinery (host side, exact).
# A 128x128 bit matrix is a list of 128 column ints: column j = M @ e_j,
# encoded as a 128-bit python int.  M @ v = XOR of columns at v's set bits.
# ----------------------------------------------------------------------------

def _state_to_int(words: Tuple[int, int, int, int]) -> int:
    v = 0
    for k, word in enumerate(words):
        v |= (word & 0xFFFFFFFF) << (32 * k)
    return v


def _int_to_state(v: int) -> Tuple[int, int, int, int]:
    return tuple((v >> (32 * k)) & 0xFFFFFFFF for k in range(4))


def _matvec(cols: List[int], v: int) -> int:
    out = 0
    while v:
        lsb = v & -v
        out ^= cols[lsb.bit_length() - 1]
        v ^= lsb
    return out


def _matmul(a_cols: List[int], b_cols: List[int]) -> List[int]:
    """(A @ B): column j of result = A @ (column j of B)."""
    return [_matvec(a_cols, bj) for bj in b_cols]


@functools.lru_cache(maxsize=None)
def step_matrix() -> Tuple[int, ...]:
    """The xorshift128 transition as 128 column ints."""
    cols = []
    for j in range(STATE_BITS):
        basis = _int_to_state(1 << j)
        cols.append(_state_to_int(step_words(*basis)))
    return tuple(cols)


@functools.lru_cache(maxsize=None)
def matrix_pow2(k: int) -> Tuple[int, ...]:
    """M**(2**k) as column ints, by repeated squaring (cached)."""
    if k == 0:
        return step_matrix()
    prev = list(matrix_pow2(k - 1))
    return tuple(_matmul(prev, prev))


def jump(words: Tuple[int, int, int, int], n: int) -> Tuple[int, int, int, int]:
    """Advance a state by n steps via binary decomposition of n (host-side)."""
    v = _state_to_int(words)
    k = 0
    n = int(n)
    while n:
        if n & 1:
            v = _matvec(list(matrix_pow2(k)), v)
        n >>= 1
        k += 1
    return _int_to_state(v)


def substream_state(words: Tuple[int, int, int, int], i: int,
                    log2_spacing: int = 64) -> Tuple[int, int, int, int]:
    """Start state of substream i: base advanced by i * 2**log2_spacing."""
    return jump(words, i << log2_spacing)


@functools.lru_cache(maxsize=None)
def lane_table(num_lanes: int, seed: Tuple[int, int, int, int] = DEFAULT_SEED,
               log2_spacing: int = 64) -> np.ndarray:
    """Start states for lanes 0..num_lanes-1, shape (num_lanes, 4) uint32.

    Lane i = substream i (spaced 2**64 apart).  Computed once host-side
    with a single matvec per lane (J = M**(2**64) applied iteratively).
    """
    J = list(matrix_pow2(log2_spacing))
    out = np.empty((num_lanes, 4), np.uint32)
    v = _state_to_int(seed)
    for i in range(num_lanes):
        out[i] = np.array(_int_to_state(v), np.uint32)
        v = _matvec(J, v)
    return out


@functools.lru_cache(maxsize=None)
def _packed_pow2_matrices(max_log2: int = 64) -> np.ndarray:
    """M**(2**k) for k in [0, max_log2) packed as uint32.

    Shape (max_log2, 128, 4): [k, row, word].  Row r of matrix k packed as
    4 uint32 words, so that output bit r = parity(popcount(row & state)).
    """
    out = np.empty((max_log2, STATE_BITS, STATE_WORDS), np.uint32)
    for k in range(max_log2):
        cols = matrix_pow2(k)
        # convert columns -> rows: row r bit j = column j bit r
        rows = [0] * STATE_BITS
        for j, col in enumerate(cols):
            c = col
            while c:
                lsb = c & -c
                r = lsb.bit_length() - 1
                rows[r] |= 1 << j
                c ^= lsb
        for r in range(STATE_BITS):
            for wd in range(STATE_WORDS):
                out[k, r, wd] = (rows[r] >> (32 * wd)) & 0xFFFFFFFF
    return out


_POPCOUNT8 = np.array([bin(i).count("1") for i in range(256)], np.uint8)


def _popcount_u32(a: np.ndarray) -> np.ndarray:
    if hasattr(np, "bitwise_count"):  # numpy >= 2.0
        return np.bitwise_count(a)
    return _POPCOUNT8[a.view(np.uint8)].reshape(a.shape + (4,)).sum(-1)


def _matvec_batch(mat: np.ndarray, states: np.ndarray) -> np.ndarray:
    """One packed GF(2) matvec over a whole state table.

    mat: (128, 4) uint32 packed rows; states: (S, 4) uint32.  Output bit r
    of each state = parity(popcount(mat[r] & state)).
    """
    acc = mat[None, :, :] & states[:, None, :]            # (S, 128, 4)
    parity = (_popcount_u32(acc).astype(np.uint32).sum(-1) & 1)  # (S, 128)
    bits = parity.reshape(states.shape[0], 4, 32).astype(np.uint32)
    return (bits << np.arange(32, dtype=np.uint32)).sum(-1, dtype=np.uint32)


def jump_batch(states: np.ndarray, n: int) -> np.ndarray:
    """Advance a whole (S, 4) uint32 state table by n steps at once.

    Vectorized numpy version of ``jump``: one packed-matrix matvec per set
    bit of ``n``, over all S lanes simultaneously — O(popcount(n)) numpy
    ops instead of O(S) python-int matvec loops.  Bit-identical to
    per-state ``jump`` (same GF(2) matrices).
    """
    states = np.asarray(states, np.uint32)
    mats = _packed_pow2_matrices(64)
    n = int(n)
    k = 0
    while n:
        if n & 1:
            states = _matvec_batch(mats[k], states)
        n >>= 1
        k += 1
    return states


def _matvec_traced(mat, s: jnp.ndarray) -> jnp.ndarray:
    """One packed GF(2) matvec in-graph: mat (128, 4); s (..., 4)."""
    acc = jnp.bitwise_and(mat, s[..., None, :])  # (..., 128, 4)
    pc = jax.lax.population_count(acc).astype(U32)
    parity = jnp.sum(pc, axis=-1) & U32(1)  # (..., 128)
    bitpos = jnp.arange(32, dtype=U32)
    bits = parity.reshape(parity.shape[:-1] + (4, 32))
    return jnp.sum(bits << bitpos, axis=-1, dtype=U32)


@functools.lru_cache(maxsize=None)
def _pow2_bit_matrices() -> np.ndarray:
    """``_packed_pow2_matrices(64)`` unpacked to 0/1 bits.

    Shape (64, 128, 128) uint8: [k, row, col], entry = bit ``col`` of
    packed row ``row`` of M**(2**k).
    """
    packed = _packed_pow2_matrices(64)
    bits = (packed[..., None] >> np.arange(32, dtype=np.uint32)) & 1
    return bits.reshape(64, STATE_BITS, STATE_BITS).astype(np.uint8)


def _gf2_matmul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Batched GF(2) product of 0/1 bf16 matrices, reduced mod 2.

    Exact: every entry is 0 or 1 and every sum is at most 128, which
    bf16 inputs and f32 accumulation hold without rounding.
    """
    prod = jnp.matmul(a, b, preferred_element_type=jnp.float32)
    return (prod.astype(jnp.int32) & 1).astype(jnp.bfloat16)


def _jump_matrix_traced(n_hi: jnp.ndarray, n_lo: jnp.ndarray) -> jnp.ndarray:
    """M**n for a dynamic 64-bit count, as a (128, 128) 0/1 bf16 matrix.

    Selects M**(2**k) where bit k of n is set and the identity where it
    is clear, then multiplies the 64 selections in a pairwise tree (six
    batched 128x128 products).  The factors are powers of M, so they
    commute and the order does not matter.  No state dimension.
    """
    shifts = jnp.arange(32, dtype=U32)
    bits = jnp.concatenate([(jnp.asarray(n_lo, U32) >> shifts) & U32(1),
                            (jnp.asarray(n_hi, U32) >> shifts) & U32(1)])
    pows = jnp.asarray(_pow2_bit_matrices(), jnp.bfloat16)  # (64, 128, 128)
    eye = jnp.eye(STATE_BITS, dtype=jnp.bfloat16)
    mats = jnp.where((bits == 1)[:, None, None], pows, eye)
    while mats.shape[0] > 1:
        pairs = mats.reshape(-1, 2, STATE_BITS, STATE_BITS)
        mats = _gf2_matmul(pairs[:, 0], pairs[:, 1])
    return mats[0]


def _pack_rows(bits: jnp.ndarray) -> jnp.ndarray:
    """(128, 128) 0/1 matrix -> (128, 4) uint32 packed rows."""
    words = bits.astype(U32).reshape(STATE_BITS, STATE_WORDS, 32)
    return jnp.sum(words << jnp.arange(32, dtype=U32), axis=-1, dtype=U32)


def jump_traced(state: jnp.ndarray, n_hi: jnp.ndarray, n_lo: jnp.ndarray
                ) -> jnp.ndarray:
    """Traced jump-ahead by a dynamic 64-bit count (n_hi, n_lo).

    ``state``: (..., 4) uint32.  Cost: one composed 128x128 GF(2) jump
    matrix M**n (``_jump_matrix_traced``, independent of the table's
    size) and one packed popcount-parity matvec over the whole table —
    used once per bulk call, never per element.
    """
    return _matvec_traced(_pack_rows(_jump_matrix_traced(n_hi, n_lo)), state)


def jump_static(state: jnp.ndarray, n: int) -> jnp.ndarray:
    """In-graph jump of a (..., 4) state table by a static count ``n``:
    one matvec per set bit of ``n`` (``jump_traced`` does one, after
    composing M**n)."""
    mats = _packed_pow2_matrices(64)
    n = int(n)
    k = 0
    while n:
        if n & 1:
            state = _matvec_traced(jnp.asarray(mats[k]), state)
        n >>= 1
        k += 1
    return state
