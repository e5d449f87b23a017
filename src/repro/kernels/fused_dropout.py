"""Pallas TPU kernel: dropout with the mask generated inline (never hits HBM).

Plain dropout reads x, reads (or writes) a mask array, writes y: >= 3
HBM round-trips of x's footprint.  ThundeRiNG's counter-addressable form
lets the kernel *regenerate* the mask bits for any element from (leaf h,
element index) alone, so the kernel is a pure read-x/write-y stream with
the full RNG pipeline (shared-root affine + XSH-RR + ctr decorrelator)
evaluated in VREGs.  This is the paper's state-sharing idea as a memory-
bandwidth optimization: one pre-advanced root state per tile (the single
multiply) plus trace-time in-tile affine tables.

Tile layout: (BM, N) row-blocks over a (M, N) 2-D view of x, so flat
element indices are contiguous per tile: p = tile_base + k, k row-major.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import lcg, sampler, splitmix, u64
from repro.core.u64 import U32


def keep_threshold(rate: float) -> int:
    """uint32 keep threshold for a drop rate: round((1-rate) * 2**32).

    The engine's bernoulli sampler threshold at p = 1 - rate: exact
    host-int arithmetic, clamped to 2**32 - 1 so a tiny positive rate
    cannot round up to 2**32 and wrap to an all-drop threshold.
    """
    return sampler.bernoulli_threshold(1.0 - rate)


def mask_elems(shape) -> int:
    """Counter elements a dropout mask over ``shape`` consumes.

    This is the lease-sizing rule for the block-delivery layer: a
    ``BlockService`` window feeding ``ops.fused_dropout`` must span at
    least this many elements of the mask stream (flat row-major
    addressing, one u32 per element — exactly the counters the kernel
    regenerates in VREGs).
    """
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _kernel(x_ref, rb_hi_ref, rb_lo_ref, cb_hi_ref, cb_lo_ref,
            h_hi_ref, h_lo_ref, a_hi_ref, a_lo_ref, c_hi_ref, c_lo_ref,
            o_ref, *, thresh: int, scale: float, n_cols: int):
    x = x_ref[...]                                   # (BM, N)
    bm = x.shape[0]
    # per-tile base root state (already advanced to ctr0 + tile offset)
    rb = (rb_hi_ref[...], rb_lo_ref[...])            # (1, 1)
    # in-tile affine expansion: root(k) = A_{k+1} * rb + C_{k+1}
    A = (a_hi_ref[...], a_lo_ref[...])               # (BM, N)
    C = (c_hi_ref[...], c_lo_ref[...])
    roots = u64.add64(u64.mul64(A, rb), C)
    h = (h_hi_ref[...], h_lo_ref[...])               # (1, 1)
    leaf = u64.add64(roots, h)
    perm = lcg.xsh_rr(leaf)
    # element counter = ctr_base + k (k row-major in-tile)
    k = (jax.lax.broadcasted_iota(U32, (bm, n_cols), 0) * U32(n_cols)
         + jax.lax.broadcasted_iota(U32, (bm, n_cols), 1))
    ctr = u64.add64((cb_hi_ref[...], cb_lo_ref[...]), (jnp.zeros_like(k), k))
    deco = splitmix.ctr_decorrelator(h, ctr)
    bits = perm ^ deco
    keep = bits < U32(thresh)
    o_ref[...] = jnp.where(keep, x * x.dtype.type(scale), jnp.zeros_like(x))


def fused_dropout_2d(x: jnp.ndarray, h, x0, ctr0, rate: float,
                     *, block_m: int = 8, interpret=False) -> jnp.ndarray:
    """Dropout on a (M, N) array; h/x0/ctr0 are u64 (hi, lo) scalar pairs.

    Element (m, n) keeps iff ThundeRiNG bits for flat counter
    ctr0 + m*N + n are below (1-rate)*2^32; kept values scale by 1/(1-rate).
    Bit-exact with ref.fused_dropout for any tiling.

    The row tile is the whole of M when M <= block_m, else block_m
    rounded down to the dtype's sublane multiple (8 for f32, 16 for
    bf16); M is padded up to a tile multiple and the pad rows dropped.
    """
    if rate <= 0.0:
        return x
    M, N = x.shape
    sub = sampler.sublane_multiple(x.dtype)
    bm = M if M <= block_m else max(sub, block_m - block_m % sub)
    Mp = -(-M // bm) * bm
    n_tiles = Mp // bm
    tile_elems = bm * N

    # Per-tile pre-advanced base roots: A(ctr0 + i*tile) x0 + C(...)
    i_idx = jnp.arange(n_tiles, dtype=U32)
    # offset = i * tile_elems as exact u64 via 32x32->64 product
    off_hi, off_lo = u64.mul32_wide(i_idx, U32(tile_elems))
    base = u64.add64((jnp.broadcast_to(ctr0[0], (n_tiles,)),
                      jnp.broadcast_to(ctr0[1], (n_tiles,))),
                     (off_hi, off_lo))
    A, C = lcg.lcg_skip_traced(base)
    rb = u64.add64(u64.mul64(A, (jnp.broadcast_to(x0[0], (n_tiles,)),
                                 jnp.broadcast_to(x0[1], (n_tiles,)))), C)

    # In-tile affine tables (trace-time constants, shared by all tiles).
    A_hi, A_lo, C_hi, C_lo = lcg.block_affine_constants(tile_elems + 1)
    At = (jnp.asarray(A_hi[1:]).reshape(bm, N), jnp.asarray(A_lo[1:]).reshape(bm, N))
    Ct = (jnp.asarray(C_hi[1:]).reshape(bm, N), jnp.asarray(C_lo[1:]).reshape(bm, N))

    thresh = keep_threshold(rate)
    scale = 1.0 / (1.0 - rate)

    # Per-tile scalars ride as (n_tiles, 1, 1) arrays so each block's
    # last two dims equal the array's — the only (1, 1) block TPU
    # tiling admits.
    per_tile = lambda v: v.reshape(n_tiles, 1, 1)
    one = lambda v: jnp.broadcast_to(v, (1, 1))
    tile_spec = pl.BlockSpec((None, 1, 1), lambda i: (i, 0, 0))
    whole = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))

    out = pl.pallas_call(
        functools.partial(_kernel, thresh=thresh, scale=scale, n_cols=N),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((bm, N), lambda i: (i, 0)),      # x
            tile_spec, tile_spec,                          # rb hi, lo
            tile_spec, tile_spec,                          # ctr base hi, lo
            whole((1, 1)), whole((1, 1)),                  # h hi, lo
            whole((bm, N)), whole((bm, N)),                # A hi, lo
            whole((bm, N)), whole((bm, N)),                # C hi, lo
        ],
        out_specs=pl.BlockSpec((bm, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, N), x.dtype),
        interpret=interpret,
    )(jnp.pad(x, ((0, Mp - M), (0, 0))), per_tile(rb[0]), per_tile(rb[1]),
      per_tile(base[0]), per_tile(base[1]),
      one(h[0]), one(h[1]),
      At[0], At[1], Ct[0], Ct[1])
    return out[:M]
