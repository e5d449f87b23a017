"""Plain reference of ThundeRiNG's faithful decorrelator mode.

Element (t, s) of the window at counter ``lo`` is

    XSH_RR(root[lo + t + 1] + h_s)  ^  w_s(lo + t + 1)

where ``w_s(n)`` is the last state word after n steps of Marsaglia's
xorshift128 substream s.  Substream s starts at the default seed advanced
by s * 2**64 steps (arXiv:2105.09578, Sec. 5.1.2).  xorshift128 is linear
over GF(2), so jumps are powers of its 128x128 bit matrix, built here from
the step itself.  Bit k of a state is bit (k % 32) of word k // 32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import ctr

SEED_WORDS = (123456789, 362436069, 521288629, 88675123)
SPACING_LOG2 = 64


def _step_words(x, y, z, w):
    t = x ^ (x << 11)
    return y, z, w, (w ^ (w >> 19)) ^ (t ^ (t >> 8))


def _words_to_bits(words: np.ndarray) -> np.ndarray:
    """(..., 4) uint32 -> (..., 128) uint8."""
    shifts = np.arange(32, dtype=np.uint32)
    b = (np.asarray(words, np.uint32)[..., None] >> shifts) & 1
    return b.reshape(words.shape[:-1] + (128,)).astype(np.uint8)


def _gf2_mul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return ((a.astype(np.int64) @ b.astype(np.int64)) & 1).astype(np.uint8)


@functools.lru_cache(maxsize=None)
def _pow2(k: int) -> np.ndarray:
    """M**(2**k) as a (128, 128) 0/1 matrix (new_bits = M @ old_bits)."""
    if k == 0:
        m = np.zeros((128, 128), np.uint8)
        for j in range(128):
            e = np.zeros(4, np.uint32)
            e[j // 32] = np.uint32(1) << np.uint32(j % 32)
            out = np.array(_step_words(*[np.uint32(v) for v in e]),
                           np.uint32)
            m[:, j] = _words_to_bits(out)
        return m
    p = _pow2(k - 1)
    return _gf2_mul(p, p)


def jump_matrix(n: int) -> np.ndarray:
    m = np.eye(128, dtype=np.uint8)
    k = 0
    while n:
        if n & 1:
            m = _gf2_mul(_pow2(k), m)
        n >>= 1
        k += 1
    return m


@jax.jit
def _apply(mt, bits):
    """bits @ M^T over GF(2); exact, since each sum is at most 128."""
    y = jnp.dot(bits, mt, precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
    return (jnp.round(y).astype(jnp.int32) & 1).astype(jnp.float32)


@jax.jit
def _to_words(bits):
    b = bits.astype(jnp.uint32).reshape(bits.shape[0], 4, 32)
    return jnp.sum(b << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                   dtype=jnp.uint32)


def start_states(cols: np.ndarray, lo: int, device=None):
    """(S, 4) uint32 states of substreams ``cols`` after ``lo`` steps."""
    cols = np.asarray(cols, np.int64)
    v0 = _words_to_bits(np.array(SEED_WORDS, np.uint32)).astype(np.float32)
    bits = jax.device_put(np.broadcast_to(v0, (len(cols), 128)).copy(),
                          device)
    top = int(cols.max()).bit_length() if len(cols) else 0
    for k in range(top):
        sel = jax.device_put(((cols >> k) & 1).astype(bool)[:, None], device)
        mt = jax.device_put(_pow2(SPACING_LOG2 + k).T.astype(np.float32),
                            device)
        bits = jnp.where(sel, _apply(mt, bits), bits)
    if lo:
        mt = jax.device_put(jump_matrix(lo).T.astype(np.float32), device)
        bits = _apply(mt, bits)
    return _to_words(bits)


@functools.partial(jax.jit, static_argnames=("rows",))
def _mismatches(blk, r0, rows, state, root_hi, root_lo, h_hi, h_lo):
    def body(s, _):
        s = _step_words(*s)
        return s, s[3]
    words = tuple(state[:, i] for i in range(4))
    end, w = jax.lax.scan(body, words, None, length=rows)
    perm = ctr.xsh_rr(ctr.limbs.add((root_hi[:, None], root_lo[:, None]),
                                    (h_hi[None, :], h_lo[None, :])))
    got = jax.lax.dynamic_slice_in_dim(blk, r0, rows, axis=0)
    return (jnp.sum((got != (perm ^ w)).astype(jnp.int32)),
            jnp.stack(end, axis=-1))


def mismatches(blk, lo: int, stream: "ctr.Stream", cols: np.ndarray, *,
               chunk: int = 256, device=None) -> int:
    """Elements of the faithful-mode block ``blk`` (window at ``lo``,
    global columns ``cols``) that differ from the reference."""
    T = blk.shape[0]
    state = start_states(cols, lo, device)
    h = tuple(jax.device_put(a, device)
              for a in ctr.limbs.split(stream.h))
    total = 0
    for r0 in range(0, T, chunk):
        rows = min(chunk, T - r0)
        roots = tuple(jax.device_put(a, device) for a in ctr.limbs.split(
            ctr.root_rows(stream.x0, lo + r0, rows)))
        n, state = _mismatches(blk, r0, rows, state, *roots, *h)
        total += int(n)
    return total
