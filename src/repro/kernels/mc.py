"""Pallas TPU kernels for the paper's two case studies (Sec. 6):
pi estimation and Black-Scholes Monte-Carlo option pricing.

Generation is FUSED into the integrand: bits are produced in VREGs,
converted to uniforms, consumed, and only a per-(tile, lane) partial
reduction leaves the kernel.  Arithmetic intensity goes from ~1 op/byte
(bulk generation: every output hits HBM) to ~(pipeline ops x draws)/4B —
the TPU counterpart of the paper's on-chip FIFO into the application
kernels (their Table 7 apps never spill random numbers to DDR either).

The generation and distribution stages are the shared sampler stages
(``repro.core.sampler``): these kernels are compositions of
``sampler.ctr_bits`` -> ``sampler.uniform_from_bits`` -> integrand, the
same stages the engine's fused sampler pipeline runs, so they stay
bit-identical with the engine-backed reference paths by construction.

Grid (T_tiles, S_tiles); each instance draws BT samples for BS lanes and
emits one (1, BS) partial (count or payoff-sum); the host sums partials.
T need not be a tile multiple: padded rows are masked out of the partial
reductions inside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import sampler as sampler_mod
from repro.core.u64 import U32

DEFAULT_BLOCK_T = 256
DEFAULT_BLOCK_S = 512


def _uniform_draw(root, ctr_rows, h):
    """One fused sampler stage: ctr-mode bits -> U[0,1) f32, in VREGs."""
    return sampler_mod.uniform_from_bits(
        sampler_mod.ctr_bits(root, ctr_rows, h))


def _row_mask(tile_rows: int, n_cols: int, block_t: int, num_steps: int):
    """(BT, BS) bool: True for rows whose global time index is < T."""
    t0 = pl.program_id(0) * block_t
    row = t0 + jax.lax.broadcasted_iota(jnp.int32, (tile_rows, n_cols), 0)
    return row < num_steps


def _pi_kernel(root_hi_ref, root_lo_ref, ctr_hi_ref, ctr_lo_ref,
               hx_hi_ref, hx_lo_ref, hy_hi_ref, hy_lo_ref, o_ref,
               *, block_t: int, num_steps: int):
    root = (root_hi_ref[...], root_lo_ref[...])
    ctr = (ctr_hi_ref[...], ctr_lo_ref[...])
    ux = _uniform_draw(root, ctr, (hx_hi_ref[...], hx_lo_ref[...]))
    uy = _uniform_draw(root, ctr, (hy_hi_ref[...], hy_lo_ref[...]))
    inside = (ux * ux + uy * uy) < 1.0
    valid = _row_mask(ux.shape[0], ux.shape[1], block_t, num_steps)
    o_ref[...] = jnp.sum((inside & valid).astype(jnp.int32), axis=0,
                         keepdims=True)


def _option_kernel(root_hi_ref, root_lo_ref, ctr_hi_ref, ctr_lo_ref,
                   hx_hi_ref, hx_lo_ref, hy_hi_ref, hy_lo_ref, o_ref,
                   *, block_t: int, num_steps: int, s0: float, strike: float,
                   r: float, sigma: float, t: float):
    root = (root_hi_ref[...], root_lo_ref[...])
    ctr = (ctr_hi_ref[...], ctr_lo_ref[...])
    u1 = _uniform_draw(root, ctr, (hx_hi_ref[...], hx_lo_ref[...]))
    u2 = _uniform_draw(root, ctr, (hy_hi_ref[...], hy_lo_ref[...]))
    z = sampler_mod.box_muller(u1, u2)
    drift = np.float32((r - 0.5 * sigma * sigma) * t)
    vol = np.float32(sigma) * jnp.sqrt(np.float32(t))
    st = np.float32(s0) * jnp.exp(drift + vol * z)
    payoff = jnp.maximum(st - np.float32(strike), 0.0) * \
        jnp.exp(np.float32(-r * t))
    valid = _row_mask(u1.shape[0], u1.shape[1], block_t, num_steps)
    payoff = jnp.where(valid, payoff, jnp.zeros_like(payoff))
    o_ref[...] = jnp.sum(payoff, axis=0, keepdims=True)


def _pad_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _launch(kernel, roots, ctr_rows, hx, hy, out_dtype, *, block_t, block_s,
            interpret):
    T = roots[0].shape[0]
    S = hx[0].shape[0]
    bt = min(block_t, _pad_to(T, 8))
    bs = min(block_s, _pad_to(S, 128))
    Tp, Sp = _pad_to(T, bt), _pad_to(S, bs)

    def pad_col(v):
        return jnp.pad(v, (0, Tp - T)).reshape(Tp, 1)

    def pad_row(v):
        return jnp.pad(v, (0, Sp - S)).reshape(1, Sp)

    grid = (Tp // bt, Sp // bs)
    col_spec = pl.BlockSpec((bt, 1), lambda i, j: (i, 0))
    row_spec = pl.BlockSpec((1, bs), lambda i, j: (0, j))
    # Partials are (T_tiles, 1, Sp): each tile's (1, bs) row is a block
    # whose second-to-last dim spans the whole (unit) axis, which TPU
    # tiling admits; a (1, bs) block of a (T_tiles, Sp) array it refuses.
    partials = pl.pallas_call(
        functools.partial(kernel, block_t=bt, num_steps=T),
        grid=grid,
        in_specs=[col_spec, col_spec, col_spec, col_spec,
                  row_spec, row_spec, row_spec, row_spec],
        out_specs=pl.BlockSpec((None, 1, bs), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((grid[0], 1, Sp), out_dtype),
        interpret=interpret,
    )(pad_col(roots[0]), pad_col(roots[1]),
      pad_col(ctr_rows[0]), pad_col(ctr_rows[1]),
      pad_row(hx[0]), pad_row(hx[1]), pad_row(hy[0]), pad_row(hy[1]))
    return partials[:, 0, :S]


def _plan_rows(px):
    """Shared-root (roots, ctr_rows) for a coordinate plan's draw window.

    The plan's counter start IS the leased window's ``ctr_lo``
    (``engine.make_plan(offset=...)``), so a ``BlockService`` lease of
    ``draws_per_lane`` steps maps 1:1 onto the kernel grid rows — MC
    consumers draw from disjoint counter windows with no per-call state.
    """
    from repro.core import engine
    return engine.root_and_ctr_rows(px.x0, px.ctr, px.num_steps)


def pi_partials_from_plans(px, py, *, block_t=DEFAULT_BLOCK_T,
                           block_s=DEFAULT_BLOCK_S,
                           interpret=False) -> jnp.ndarray:
    """``pi_partials`` addressed by two engine plans (x/y coordinate
    families of one shared root, any counter window)."""
    roots, ctr_rows = _plan_rows(px)
    return pi_partials(roots, ctr_rows, px.h, py.h, block_t=block_t,
                       block_s=block_s, interpret=interpret)


def option_partials_from_plans(px, py, *, s0, strike, r, sigma, t,
                               block_t=DEFAULT_BLOCK_T,
                               block_s=DEFAULT_BLOCK_S,
                               interpret=False) -> jnp.ndarray:
    """``option_partials`` addressed by two engine plans."""
    roots, ctr_rows = _plan_rows(px)
    return option_partials(roots, ctr_rows, px.h, py.h, s0=s0, strike=strike,
                           r=r, sigma=sigma, t=t, block_t=block_t,
                           block_s=block_s, interpret=interpret)


def pi_partials(roots, ctr_rows, hx, hy, *, block_t=DEFAULT_BLOCK_T,
                block_s=DEFAULT_BLOCK_S, interpret=False) -> jnp.ndarray:
    """(T_tiles, S) int32 in-circle partial counts."""
    return _launch(_pi_kernel, roots, ctr_rows, hx, hy, jnp.int32,
                   block_t=block_t, block_s=block_s, interpret=interpret)


def option_partials(roots, ctr_rows, hx, hy, *, s0, strike, r, sigma, t,
                    block_t=DEFAULT_BLOCK_T, block_s=DEFAULT_BLOCK_S,
                    interpret=False) -> jnp.ndarray:
    """(T_tiles, S) f32 partial discounted-payoff sums."""
    kern = functools.partial(_option_kernel, s0=s0, strike=strike, r=r,
                             sigma=sigma, t=t)
    return _launch(kern, roots, ctr_rows, hx, hy, jnp.float32,
                   block_t=block_t, block_s=block_s, interpret=interpret)
