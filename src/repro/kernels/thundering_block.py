"""Pallas TPU kernel: bulk ThundeRiNG block generation, (T, S) time-major.

This is the executor behind the engine's "pallas" backend
(``repro.core.engine``); build a ``GenPlan`` and call ``engine.generate``
rather than invoking ``block_ctr``/``block_faithful`` directly.

The FPGA architecture (Fig. 3) maps onto the TPU grid as:

  RSGU (root state generation)  ->  done OUTSIDE the kernel with the
      two-level jump-ahead (`lcg.root_states_vector`): exactly one 64-bit
      multiply per time step *total*, shared by all S streams — the paper's
      "one multiplier for any number of instances".  The (T,) root-state
      vector is streamed into the kernel as a (BT, 1) block per tile.
  SOU daisy chain               ->  S lanes.  Leaf transition is a
      broadcast add (BT,1)+(1,BS); the XSH-RR permutation is elementwise.
  Decorrelator                  ->  two modes:
      * "ctr"       fully parallel splitmix counter decorrelator (TPU-native,
                    beyond-paper; see DESIGN.md).
      * "faithful"  serial xorshift128 per stream, vectorized across lanes
                    and stepped BT times per tile — the FPGA dataflow with
                    time rotated onto the sublane axis.  Per-tile start
                    states are pre-jumped with the GF(2) matrix (outside).
  FIFO into consumer            ->  the fused *sampler* output stage
      (``repro.core.sampler``): uniform / normal / bernoulli transforms
      run on the uint32 tile while it is still in VMEM, so raw bits never
      reach HBM and a bfloat16 output halves the written bytes — the
      paper's never-spill-raw-numbers dataflow (Table 7).

VMEM per tile (defaults BT=256, BS=512): out 512 KB + ~6 u32 temporaries
of the same shape ~ 3.5 MB, comfortably inside 16 MB.  Lane dim BS is a
multiple of 128, sublane dim BT a multiple of 8 (16 for bfloat16 output,
32 for bool — see ``tile_t``).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import lcg, sampler as sampler_mod, u64, xorshift

DEFAULT_BLOCK_T = 256
DEFAULT_BLOCK_S = 512

BITS: Tuple[str, None] = ("bits", None)


def _ctr_kernel(root_hi_ref, root_lo_ref, ctr_hi_ref, ctr_lo_ref,
                h_hi_ref, h_lo_ref, o_ref, *, deco: str = "splitmix64",
                sampler=BITS, out_dtype: str = "float32"):
    rh, rl = root_hi_ref[...], root_lo_ref[...]      # (BT, 1)
    hh, hl = h_hi_ref[...], h_lo_ref[...]            # (1, BS)
    ch, cl = ctr_hi_ref[...], ctr_lo_ref[...]        # (BT, 1)
    bits = sampler_mod.ctr_bits((rh, rl), (ch, cl), (hh, hl), deco=deco)
    # Sampler output stage fused in-VMEM: the uint32 block never leaves
    # the kernel, only the (possibly half-width) samples hit HBM.
    o_ref[...] = sampler_mod.apply(bits, sampler, out_dtype,
                                   roll=pltpu.roll)


def _faithful_kernel(root_hi_ref, root_lo_ref, h_hi_ref, h_lo_ref,
                     xs_ref, o_ref, *refs, block_t: int, sampler=BITS,
                     out_dtype: str = "float32"):
    # With a non-bits sampler the uint32 block accumulates in a VMEM
    # scratch buffer (o_ref holds the transformed dtype); with "bits" the
    # output ref itself is the accumulator, as before.
    bits_ref = refs[0] if refs else o_ref
    rh, rl = root_hi_ref[...], root_lo_ref[...]      # (BT, 1)
    hh, hl = h_hi_ref[...], h_lo_ref[...]            # (1, BS)
    leaf = u64.add64((rh, rl), (hh, hl))
    bits_ref[...] = lcg.xsh_rr(leaf)                 # permuted, pre-XOR

    # Serial decorrelator: advance xorshift128 once per sublane row — the
    # FPGA's one-output-per-cycle LFSR, vectorized across BS lanes.
    x = xs_ref[0, 0, :]
    y = xs_ref[0, 1, :]
    z = xs_ref[0, 2, :]
    w = xs_ref[0, 3, :]

    def body(t, carry):
        x, y, z, w = carry
        x, y, z, w = xorshift.step_xyzw(x, y, z, w)
        bits_ref[pl.ds(t, 1), :] = bits_ref[pl.ds(t, 1), :] ^ w[None, :]
        return x, y, z, w

    jax.lax.fori_loop(0, block_t, body, (x, y, z, w))
    if refs:
        o_ref[...] = sampler_mod.apply(bits_ref[...], sampler, out_dtype,
                                       roll=pltpu.roll)


def _pad_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def tile_t(block_t: int, T: int, dtype) -> int:
    """Row-tile size: a multiple of the out dtype's min sublane tile (8
    for f32/u32, 16 for bf16, 32 for bool) — in particular always even,
    so Box-Muller row pairs never straddle a tile boundary.  A requested
    ``block_t`` that is not a multiple is rounded DOWN (never below one
    sublane tile): an odd tile height would flip the pairing parity of
    every subsequent tile."""
    sub = sampler_mod.sublane_multiple(dtype)
    bt = min(block_t, _pad_to(T, sub))
    return max(sub, bt - bt % sub)


def block_ctr(roots, ctr_rows, h, *, block_t=DEFAULT_BLOCK_T,
              block_s=DEFAULT_BLOCK_S, interpret=False,
              deco: str = "splitmix64", sampler=BITS,
              out_dtype: str = "float32") -> jnp.ndarray:
    """(T, S) block via the ctr-mode kernel; dtype set by ``sampler``.

    roots: ((T,), (T,)) u32 root states; ctr_rows: ((T,), (T,)) per-row
    counters; h: ((S,), (S,)) leaf offsets.  ``sampler`` is a parsed
    ``repro.core.sampler`` spec tuple; its output stage runs inside the
    kernel, so only the transformed samples are ever written to HBM.
    """
    T = roots[0].shape[0]
    S = h[0].shape[0]
    dtype = sampler_mod.result_dtype(sampler, out_dtype)
    bt = tile_t(block_t, T, dtype)
    bs = min(block_s, _pad_to(S, 128))
    Tp, Sp = _pad_to(T, bt), _pad_to(S, bs)

    def pad_col(v):  # (T,) -> (Tp, 1)
        return jnp.pad(v, (0, Tp - T)).reshape(Tp, 1)

    def pad_row(v):  # (S,) -> (1, Sp)
        return jnp.pad(v, (0, Sp - S)).reshape(1, Sp)

    grid = (Tp // bt, Sp // bs)
    out = pl.pallas_call(
        functools.partial(_ctr_kernel, deco=deco, sampler=sampler,
                          out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bs), lambda i, j: (0, j)),
            pl.BlockSpec((1, bs), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bt, bs), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Tp, Sp), dtype),
        interpret=interpret,
    )(pad_col(roots[0]), pad_col(roots[1]),
      pad_col(ctr_rows[0]), pad_col(ctr_rows[1]),
      pad_row(h[0]), pad_row(h[1]))
    return out[:T, :S]


def block_faithful(roots, h, xs_tile_states, *, block_t=DEFAULT_BLOCK_T,
                   block_s=DEFAULT_BLOCK_S, interpret=False, sampler=BITS,
                   out_dtype: str = "float32") -> jnp.ndarray:
    """(T, S) block via the faithful serial-xorshift kernel.

    xs_tile_states: (T//bt, 4, S) uint32 — per (row-tile, stream) xorshift
    state at the tile's first step (pre-jumped via the GF(2) matrix).
    The caller's bt must match ``tile_t(block_t, T, dtype)``.
    """
    T = roots[0].shape[0]
    S = h[0].shape[0]
    dtype = sampler_mod.result_dtype(sampler, out_dtype)
    bt = tile_t(block_t, T, dtype)
    bs = min(block_s, _pad_to(S, 128))
    Tp, Sp = _pad_to(T, bt), _pad_to(S, bs)
    n_t = Tp // bt
    assert xs_tile_states.shape == (n_t, 4, S), xs_tile_states.shape
    xs = jnp.pad(xs_tile_states, ((0, 0), (0, 0), (0, Sp - S)))

    def pad_col(v):
        return jnp.pad(v, (0, Tp - T)).reshape(Tp, 1)

    def pad_row(v):
        return jnp.pad(v, (0, Sp - S)).reshape(1, Sp)

    grid = (n_t, Sp // bs)
    scratch = [] if sampler == BITS else [pltpu.VMEM((bt, bs), jnp.uint32)]
    out = pl.pallas_call(
        functools.partial(_faithful_kernel, block_t=bt, sampler=sampler,
                          out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bs), lambda i, j: (0, j)),
            pl.BlockSpec((1, bs), lambda i, j: (0, j)),
            pl.BlockSpec((1, 4, bs), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((bt, bs), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Tp, Sp), dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(pad_col(roots[0]), pad_col(roots[1]), pad_row(h[0]), pad_row(h[1]), xs)
    return out[:T, :S]
