"""Public jit'd wrappers around the unified RNG engine and Pallas kernels.

All bulk generation is expressed as an ``engine.GenPlan`` and dispatched
through ``repro.core.engine`` — the same plan runs on the "ref" (jnp
oracle), "xla" (fused elementwise) and "pallas" (tiled kernel) backends
bit-identically.  On CPU (this container) the Pallas backend runs under
``interpret=True``; on TPU the same code lowers through Mosaic.

Entry points:
  * ``thundering_bulk``   — (T, S) bulk MISRN block, mode "ctr"/"faithful"
  * ``fused_dropout``     — dropout with inline mask generation
  * ``estimate_pi``       — fused Monte-Carlo pi (paper Sec. 6 app 1)
  * ``price_option``      — fused Black-Scholes MC (paper Sec. 6 app 2)
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import engine, stream as stream_mod
from repro.kernels import fused_dropout as _fd
from repro.kernels import mc as _mc


_use_interpret = engine.use_interpret


def h_table(seed: int, num_streams: int, purpose: int = 0
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(S,) even leaf offsets h_s, derived the same way ThunderStream.derive
    does (one shared helper: engine.derive_leaf), so bulk blocks and the
    stream API live in the same MISRN family."""
    _, h_fam = engine.family_from_seed(seed, purpose)
    return engine.leaf_table(h_fam, num_streams)


@functools.partial(jax.jit, static_argnames=(
    "num_streams", "num_steps", "mode", "offset", "seed", "block_t",
    "block_s", "use_kernel", "deco", "backend", "sampler", "out_dtype"))
def thundering_bulk(*, seed: int, num_streams: int, num_steps: int,
                    mode: str = "ctr", offset: int = 0,
                    block_t: int = engine.DEFAULT_BLOCK_T,
                    block_s: int = engine.DEFAULT_BLOCK_S,
                    use_kernel: bool = True,
                    deco: str = "splitmix64",
                    backend: Optional[str] = None,
                    sampler: str = "bits",
                    out_dtype: str = "float32") -> jnp.ndarray:
    """(num_steps, num_streams) MISRN block (time-major).

    ``sampler``/``out_dtype`` select the fused output stage (uint32 bits
    by default; see ``repro.core.sampler``).  ``backend`` names an engine
    backend explicitly; otherwise ``use_kernel`` keeps its historical
    meaning (True -> "pallas", False -> "ref").
    """
    plan = engine.make_plan(seed=seed, num_streams=num_streams,
                            num_steps=num_steps, offset=offset, mode=mode,
                            deco=deco, sampler=sampler, out_dtype=out_dtype)
    be = backend or ("pallas" if use_kernel else "ref")
    return engine.generate(plan, backend=be, block_t=block_t,
                           block_s=block_s)


def fused_dropout(x: jnp.ndarray, stream, rate: float, *, block_m: int = 8,
                  use_kernel: bool = True) -> jnp.ndarray:
    """Dropout over arbitrary-shape x, mask addressed by (stream, flat idx).

    The same (stream, counter) always produces the same mask regardless of
    tiling/sharding — deterministic under resharding and elastic restarts.
    The mask bits are the stream's engine plan; the kernel path fuses their
    generation into the read-x/write-y stream (mask never hits HBM).

    ``stream`` may also be a ``BlockService`` lease (``runtime.blocks``):
    the mask is then addressed by the lease's channel stream at its
    window start, and the window must cover ``fused_dropout.mask_elems(
    x.shape)`` elements — leased masks make re-using dropout randomness
    across layers/steps a structural error instead of a bug hunt.
    """
    if not isinstance(stream, stream_mod.ThunderStream):
        lease = stream
        if lease.length < _fd.mask_elems(x.shape):
            raise ValueError(
                f"lease window [{lease.lo}, {lease.hi}) is smaller than the "
                f"{_fd.mask_elems(x.shape)}-element mask for shape {x.shape}")
        stream = lease.stream()
    if rate <= 0.0:
        return x
    shape = x.shape
    n = x.size
    last = shape[-1] if len(shape) >= 1 else 1
    x2 = x.reshape(n // last, last)
    if not use_kernel:
        # keep mask = engine bernoulli sampler at p = 1 - rate: the same
        # exact host-int threshold as the kernel's keep_threshold.
        plan = engine.plan_for_stream(stream, n,
                                      sampler=f"bernoulli({1.0 - rate!r})")
        keep = engine.generate_flat(plan).reshape(x2.shape)
        scale = jnp.asarray(1.0 / (1.0 - rate), x.dtype)
        out = jnp.where(keep, x2 * scale, jnp.zeros_like(x2))
        return out.reshape(shape)
    h = (stream.h_hi, stream.h_lo)
    x0 = (stream.x0_hi, stream.x0_lo)
    ctr0 = (stream.ctr_hi, stream.ctr_lo)
    out = _fd.fused_dropout_2d(x2, h, x0, ctr0, rate, block_m=block_m,
                               interpret=_use_interpret())
    return out.reshape(shape)


def _mc_plans(seed: int, num_lanes: int, draws_per_lane: int,
              purpose_x: int, purpose_y: int, offset: int = 0):
    """Two engine plans (x/y coordinate stream families, shared root).

    ``offset`` is the draw-window start: counter rows ``[offset,
    offset + draws_per_lane)`` — the window a ``BlockService`` lease
    hands out, so repeated app calls never re-spend randomness.
    """
    px = engine.make_plan(seed=seed, num_streams=num_lanes,
                          num_steps=draws_per_lane, purpose=purpose_x,
                          offset=offset)
    py = engine.make_plan(seed=seed, num_streams=num_lanes,
                          num_steps=draws_per_lane, purpose=purpose_y,
                          offset=offset)
    return px, py


@functools.partial(jax.jit, static_argnames=(
    "seed", "num_lanes", "draws_per_lane", "block_t", "block_s",
    "use_kernel", "offset"))
def estimate_pi(*, seed: int, num_lanes: int, draws_per_lane: int,
                offset: int = 0,
                block_t: int = _mc.DEFAULT_BLOCK_T,
                block_s: int = _mc.DEFAULT_BLOCK_S,
                use_kernel: bool = True) -> jnp.ndarray:
    """Monte-Carlo pi over num_lanes independent stream pairs (paper Fig. 8)."""
    px, py = _mc_plans(seed, num_lanes, draws_per_lane, 1, 2, offset)
    if use_kernel:
        partials = _mc.pi_partials_from_plans(px, py, block_t=block_t,
                                              block_s=block_s,
                                              interpret=_use_interpret())
        # exact int32 per-lane counts, then the ref path's float32 sum
        lanes = jnp.sum(partials, axis=0)
        inside = jnp.sum(lanes.astype(jnp.float32))
    else:
        from repro.kernels import ref
        ux = engine.sample(px, sampler="uniform", backend="ref")
        uy = engine.sample(py, sampler="uniform", backend="ref")
        inside = jnp.sum(ref.mc_pi_from_uniforms(ux, uy).astype(jnp.float32))
    total = num_lanes * draws_per_lane
    return 4.0 * inside / total


@functools.partial(jax.jit, static_argnames=(
    "seed", "num_lanes", "draws_per_lane", "s0", "strike", "r", "sigma",
    "t", "block_t", "block_s", "use_kernel", "offset"))
def price_option(*, seed: int, num_lanes: int, draws_per_lane: int,
                 offset: int = 0,
                 s0: float = 100.0, strike: float = 100.0, r: float = 0.05,
                 sigma: float = 0.2, t: float = 1.0,
                 block_t: int = _mc.DEFAULT_BLOCK_T,
                 block_s: int = _mc.DEFAULT_BLOCK_S,
                 use_kernel: bool = True) -> jnp.ndarray:
    """European call price via GBM Monte-Carlo (paper Fig. 9 / Table 7)."""
    px, py = _mc_plans(seed, num_lanes, draws_per_lane, 3, 4, offset)
    if use_kernel:
        partials = _mc.option_partials_from_plans(
            px, py, s0=s0, strike=strike, r=r, sigma=sigma, t=t,
            block_t=block_t, block_s=block_s, interpret=_use_interpret())
        payoff_sum = jnp.sum(partials)
    else:
        from repro.kernels import ref
        u1 = engine.sample(px, sampler="uniform", backend="ref")
        u2 = engine.sample(py, sampler="uniform", backend="ref")
        payoff_sum = jnp.sum(ref.mc_option_from_uniforms(
            u1, u2, s0, strike, r, sigma, t))
    total = num_lanes * draws_per_lane
    return payoff_sum / total
