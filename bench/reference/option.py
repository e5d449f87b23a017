"""Plain reference of Monte Carlo pricing of a European call under GBM.

Path (t, s) of the window at counter ``lo`` draws u1 from stream s of the
x family and u2 from stream s of the y family, both ctr-mode bits of one
root (see ``ctr``), as uniforms from their top 24 bits.  Box-Muller's
cosine branch turns them into z, and the discounted payoff is

    max(s0 * exp((r - sigma**2 / 2) * T + sigma * sqrt(T) * z) - K, 0)
        * exp(-r * T).

The result is each stream's (lane's) payoff sum over the window's rows.
``dtype`` sets the precision of the per-path arithmetic; sums accumulate
in float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import ctr

TINY = np.float32(1.1754944e-38)


def _uniform(b, dtype):
    u = (b >> 8).astype(jnp.int32).astype(jnp.float32) * np.float32(2 ** -24)
    return u.astype(dtype)


@functools.partial(jax.jit, static_argnames=("dtype", "params"))
def _lane_sums(root_hi, root_lo, step_hi, step_lo, hx, hkx, hy, hky, *,
               dtype, params):
    s0, k, r, sigma, t = params
    root, step = (root_hi, root_lo), (step_hi, step_lo)
    u1 = _uniform(ctr.bits(root, step, hx, hkx), dtype)
    u2 = _uniform(ctr.bits(root, step, hy, hky), dtype)
    c = lambda v: jnp.asarray(v, dtype)  # noqa: E731
    rad = jnp.sqrt(c(-2.0) * jnp.log(jnp.maximum(u1, c(TINY))))
    z = rad * jnp.cos(c(2.0 * math.pi) * u2)
    st = c(s0) * jnp.exp(c((r - 0.5 * sigma * sigma) * t)
                         + c(sigma * math.sqrt(t)) * z)
    pay = jnp.maximum(st - c(k), c(0.0)) * c(math.exp(-r * t))
    return jnp.sum(pay.astype(jnp.float32), axis=0)


def lane_sums(lo: int, rows: int, x: "ctr.Stream", y: "ctr.Stream",
              params, *, dtype=jnp.float32, chunk: int = 256):
    """(S,) float32 per-lane payoff sums of the window [lo, lo + rows)."""
    cx = x.column_args()
    cy = y.column_args()
    hx, hkx = (cx[0], cx[1]), (cx[2], cx[3])
    hy, hky = (cy[0], cy[1]), (cy[2], cy[3])
    total = None
    for r0 in range(0, rows, chunk):
        n = min(chunk, rows - r0)
        part = _lane_sums(*x.row_args(lo + r0, n), hx, hkx, hy, hky,
                          dtype=dtype, params=tuple(params))
        total = part if total is None else total + part
    return total
