"""Unified RNG engine: one backend-dispatched generation substrate.

The paper's architecture is a *plan*, not an implementation: one shared
root-state generator (RSGU) feeds any number of cheap per-stream output
units (SOU + decorrelator).  This module makes that split explicit in
software.  A ``GenPlan`` describes WHAT to generate —

  (x0, h-table, counter window, (T, S) shape, decorrelator mode,
   sampler output stage)

— and a pluggable backend decides HOW:

  * ``"ref"``     the pure-jnp oracles in ``repro.kernels.ref`` (validated
                  against the numpy golden; slow, simple, always right),
  * ``"xla"``     the engine's own fused elementwise arithmetic (what
                  ``stream.random_bits`` always compiled to),
  * ``"pallas"``  the tiled TPU kernels in ``repro.kernels.thundering_block``
                  (``interpret=True`` on CPU, Mosaic on TPU).

All backends are bit-exact for both decorrelator modes, so the choice is
purely a performance decision; ``select_backend`` picks one from the plan
shape and platform, and every entry point takes a per-call override.

The plan's *sampler* field (``repro.core.sampler``) fuses distribution
shaping into generation — uniform / Box-Muller normal / exact-threshold
bernoulli plus the programmable distribution stages exponential(rate),
poisson(rate), gamma(shape) and categorical[w0,w1,...], float32 or
bfloat16 — applied in-VMEM by the Pallas kernels and as fused
elementwise arithmetic by ref/xla, so raw uint32 blocks never
round-trip through HBM on the way to a float consumer.
``sample(plan, sampler=...)`` is the per-call override.

``generate_sharded`` is the multi-device analogue of the paper's instance
scaling: the (T, S) block is split over a mesh by the stream axis with
``shard_map``.  Because every element is counter-addressable — a pure
function of (x0, h_s, ctr + t) — each device generates its column slice
from the replicated root state with ZERO cross-device communication,
exactly as adding SOU instances on the FPGA costs no extra root-generator
hardware.

This module is the single home of the shared plumbing of
``core/stream.py`` and ``kernels/ops.py``: family/leaf-offset
derivation (``family_from_seed``, ``derive_leaf``, ``leaf_table``),
root-state/counter-row expansion (``root_and_ctr_rows``) and the
xorshift128 start-state prep for the faithful decorrelator.

Import layering: ``engine`` sits in ``repro.core`` and imports only the
arithmetic cores (lcg/splitmix/u64/xorshift); the kernel modules are
imported lazily inside backends.  ``stream.py`` and ``kernels/ops.py``
import the engine, never the other way around.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lcg, sampler as sampler_mod, splitmix, u64, xorshift
from repro.core.u64 import U32, U64Pair

DEFAULT_BLOCK_T = 256
DEFAULT_BLOCK_S = 512

_M64 = (1 << 64) - 1


# ---------------------------------------------------------------------------
# Family / leaf-offset derivation (the ONE copy; stream.derive and
# ops.h_table used to each have their own)
# ---------------------------------------------------------------------------

def family_from_seed(seed: int, purpose: int = 0) -> Tuple[U64Pair, U64Pair]:
    """(x0, h_family) for a python-int seed.

    ``x0`` is the shared root base state (one per family — the paper's
    RSGU seed); ``h_family`` is the family's even leaf offset from which
    per-stream offsets derive.  ``purpose`` selects disjoint h families
    over the same root (e.g. the x/y coordinate streams of the MC apps).
    """
    x0 = splitmix.splitmix64_host(seed & _M64, 0x1234)
    h = (splitmix.splitmix64_host(seed, purpose) << 1) & _M64
    x0_hi, x0_lo = (u64.to_u32(v) for v in u64.const64(x0))
    h_hi, h_lo = (u64.to_u32(v) for v in u64.const64(h))
    return (x0_hi, x0_lo), (h_hi, h_lo)


def derive_leaf(h_parent: U64Pair, tag: U64Pair) -> U64Pair:
    """Child leaf offset: splitmix64(h_parent, tag) forced even (<< 1).

    Even offsets keep the Hull-Dobell full-period condition (lcg.py doc);
    splitmix keeps distinct tags in distinct streams.  ``tag`` limbs may
    be scalars or vectors (broadcast against ``h_parent``).
    """
    return u64.shl64(splitmix.splitmix64(h_parent, tag), 1)


def leaf_table(h_family: U64Pair, num_streams: int) -> U64Pair:
    """(S,) even leaf offsets h_s for streams 0..S-1 of a family."""
    sid = jnp.arange(num_streams, dtype=U32)
    return derive_leaf((jnp.broadcast_to(h_family[0], sid.shape),
                        jnp.broadcast_to(h_family[1], sid.shape)),
                       (jnp.zeros_like(sid), sid))


# ---------------------------------------------------------------------------
# GenPlan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GenPlan:
    """One bulk generation request: a (T, S) uint32 block.

    x0        (hi, lo) scalar root base state (may be traced).
    h         (hi, lo) arrays of shape (S,): per-stream leaf offsets.
    num_steps T, the time extent.
    ctr       (hi, lo) scalar counter start (may be traced).
    offset    the counter start as a static python int when known at
              trace time (enables host-exact xorshift jumps for the
              faithful decorrelator), else None.
    mode      "ctr" (counter decorrelator, pure map) or "faithful"
              (paper's serial xorshift128 decorrelator).
    deco      ctr-mode hash: "splitmix64" (default) or "fmix32".
    sampler   output stage: "bits" (default), "uniform", "normal"
              (Box-Muller over adjacent row pairs; T must be even),
              "bernoulli(p)", or a distribution stage —
              "exponential(rate)", "poisson(rate)", "gamma(shape)",
              "categorical[w0,w1,...]" (all elementwise, any T).
              Grammar in ``repro.core.sampler.SPEC_GRAMMAR``.
    out_dtype "float32" or "bfloat16" for the float samplers (bits is
              always uint32, bernoulli always bool; distribution counts
              and category indices are float-coded exact integers).

    Example:
        >>> from repro.core import engine
        >>> plan = engine.make_plan(seed=7, num_streams=4, num_steps=8)
        >>> plan.shape                    # (T, S), time-major
        (8, 4)
        >>> (plan.mode, plan.deco, plan.sampler)
        ('ctr', 'splitmix64', 'bits')
    """
    x0: U64Pair
    h: U64Pair
    num_steps: int
    ctr: U64Pair
    offset: Optional[int] = 0
    mode: str = "ctr"
    deco: str = "splitmix64"
    sampler: str = "bits"
    out_dtype: str = "float32"

    @property
    def num_streams(self) -> int:
        return int(self.h[0].shape[0])

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.num_steps, self.num_streams)


def make_plan(*, seed: int, num_streams: int, num_steps: int, offset: int = 0,
              purpose: int = 0, mode: str = "ctr",
              deco: str = "splitmix64", sampler: str = "bits",
              out_dtype: str = "float32") -> GenPlan:
    """Plan for a (T, S) block of the family derived from ``seed``."""
    x0, h_fam = family_from_seed(seed, purpose)
    ch, cl = u64.const64(offset)
    return GenPlan(x0=x0, h=leaf_table(h_fam, num_streams),
                   num_steps=num_steps, ctr=(u64.to_u32(ch), u64.to_u32(cl)),
                   offset=offset, mode=mode, deco=deco, sampler=sampler,
                   out_dtype=out_dtype)


def plan_for_stream(stream, num_steps: int, mode: str = "ctr",
                    deco: str = "splitmix64", sampler: str = "bits",
                    out_dtype: str = "float32") -> GenPlan:
    """Plan for ``num_steps`` elements of ONE ThunderStream (S = 1).

    The stream's counter is traced state, so ``offset`` is None; backends
    that need host-exact jumps fall back to traced GF(2) jumps.
    """
    return GenPlan(x0=(stream.x0_hi, stream.x0_lo),
                   h=(jnp.reshape(stream.h_hi, (1,)),
                      jnp.reshape(stream.h_lo, (1,))),
                   num_steps=num_steps,
                   ctr=(stream.ctr_hi, stream.ctr_lo),
                   offset=None, mode=mode, deco=deco, sampler=sampler,
                   out_dtype=out_dtype)


# ---------------------------------------------------------------------------
# Shared prep helpers
# ---------------------------------------------------------------------------

def root_and_ctr_rows(x0: U64Pair, ctr: U64Pair, num_steps: int
                      ) -> Tuple[U64Pair, U64Pair]:
    """((T,) root states for ctr+1..ctr+T, (T,) per-row counters ctr+t)."""
    roots = lcg.root_states_vector(x0, ctr, num_steps)
    t_idx = jnp.arange(num_steps, dtype=U32)
    ctr_rows = u64.add64((jnp.broadcast_to(ctr[0], t_idx.shape),
                          jnp.broadcast_to(ctr[1], t_idx.shape)),
                         (jnp.zeros_like(t_idx), t_idx))
    return roots, ctr_rows


def _faithful_start_states(plan: GenPlan) -> jnp.ndarray:
    """(S, 4) xorshift128 states of substreams 0..S-1 advanced to plan.ctr.

    Static offsets use the host-exact GF(2) jump (trace-time constants);
    traced counters use the in-graph jump (bit-identical; see
    tests/test_xorshift.py::test_jump_traced_matches_host).
    """
    S = plan.num_streams
    tbl = xorshift.lane_table(S)
    if plan.offset is not None:
        if plan.offset:
            tbl = xorshift.jump_batch(tbl, plan.offset)
        return jnp.asarray(tbl)
    return xorshift.jump_traced(jnp.asarray(tbl), plan.ctr[0], plan.ctr[1])


def _faithful_tile_states(plan: GenPlan, block_t: int, n_tiles: int,
                          xs0: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """(n_tiles, 4, S) per-(row-tile, stream) xorshift start states.

    Tile ``i`` starts ``i * block_t`` steps past ``plan.ctr``.  The
    states are chained in-graph from the (S, 4) states at ``plan.ctr``,
    each one static ``block_t`` jump past the last.  ``xs0`` supplies
    those states where substream identity follows the GLOBAL stream
    index (the sharded case); otherwise they are
    ``_faithful_start_states(plan)``.
    """
    if xs0 is None:
        xs0 = _faithful_start_states(plan)
    states = [xs0]
    for _ in range(n_tiles - 1):
        states.append(xorshift.jump_static(states[-1], block_t))
    return jnp.transpose(jnp.stack(states), (0, 2, 1))  # (n_tiles, 4, S)


def _leaf_permuted(roots: U64Pair, h: U64Pair) -> jnp.ndarray:
    """XSH_RR(root_t + h_s): (T,) roots x (S,) offsets -> (T, S) uint32."""
    leaf = u64.add64((roots[0][:, None], roots[1][:, None]),
                     (h[0][None, :], h[1][None, :]))
    return lcg.xsh_rr(leaf)


def _deco_fn(deco: str) -> Callable[[U64Pair, U64Pair], jnp.ndarray]:
    if deco == "splitmix64":
        return splitmix.ctr_decorrelator
    if deco == "fmix32":
        return splitmix.ctr_decorrelator32
    raise ValueError(f"unknown deco {deco!r}")


# ---------------------------------------------------------------------------
# Backends
# ---------------------------------------------------------------------------

_BACKENDS: Dict[str, Callable] = {}


def register_backend(name: str):
    """Decorator: register fn(plan, *, block_t, block_s, xs0) -> (T, S)."""
    def deco(fn):
        _BACKENDS[name] = fn
        return fn
    return deco


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_BACKENDS))


def use_interpret() -> bool:
    """True when Pallas kernels must run under the interpreter (no TPU)."""
    return jax.default_backend() != "tpu"


@register_backend("ref")
def _ref_backend(plan: GenPlan, *, block_t: int, block_s: int,
                 xs0: Optional[jnp.ndarray]) -> jnp.ndarray:
    from repro.kernels import ref
    if plan.mode == "ctr":
        bits = ref.thundering_block_ctr(plan.x0, plan.h, plan.num_steps,
                                        plan.ctr, deco=plan.deco)
    elif plan.mode == "faithful":
        if xs0 is None:
            xs0 = _faithful_start_states(plan)
        bits = ref.thundering_block_faithful(plan.x0, plan.h, plan.num_steps,
                                             xs0, plan.ctr)
    else:
        raise ValueError(f"unknown mode {plan.mode!r}")
    return sampler_mod.apply(bits, sampler_mod.parse(plan.sampler),
                             plan.out_dtype)


@register_backend("xla")
def _xla_backend(plan: GenPlan, *, block_t: int, block_s: int,
                 xs0: Optional[jnp.ndarray]) -> jnp.ndarray:
    T, S = plan.shape
    roots, ctr_rows = root_and_ctr_rows(plan.x0, plan.ctr, T)
    permuted = _leaf_permuted(roots, plan.h)
    if plan.mode == "ctr":
        dec = _deco_fn(plan.deco)(
            (jnp.broadcast_to(plan.h[0][None, :], (T, S)),
             jnp.broadcast_to(plan.h[1][None, :], (T, S))),
            (jnp.broadcast_to(ctr_rows[0][:, None], (T, S)),
             jnp.broadcast_to(ctr_rows[1][:, None], (T, S))))
        bits = permuted ^ dec
    elif plan.mode == "faithful":
        if xs0 is None:
            xs0 = _faithful_start_states(plan)

        def body(state, perm_row):
            x, y, z, w = (state[..., i] for i in range(4))
            x, y, z, w = xorshift.step_xyzw(x, y, z, w)
            return jnp.stack([x, y, z, w], -1), perm_row ^ w

        _, bits = jax.lax.scan(body, xs0, permuted)
    else:
        raise ValueError(f"unknown mode {plan.mode!r}")
    # XLA fuses the sampler stage into the generation elementwise graph;
    # the barrier only matters for normal's pairing rolls (see sampler).
    return sampler_mod.apply(bits, sampler_mod.parse(plan.sampler),
                             plan.out_dtype, barrier=True)


def _pad_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


@register_backend("pallas")
def _pallas_backend(plan: GenPlan, *, block_t: int, block_s: int,
                    xs0: Optional[jnp.ndarray]) -> jnp.ndarray:
    from repro.kernels import thundering_block as _tb
    T = plan.num_steps
    spec = sampler_mod.parse(plan.sampler)
    roots, ctr_rows = root_and_ctr_rows(plan.x0, plan.ctr, T)
    if plan.mode == "ctr":
        return _tb.block_ctr(roots, ctr_rows, plan.h, block_t=block_t,
                             block_s=block_s, interpret=use_interpret(),
                             deco=plan.deco, sampler=spec,
                             out_dtype=plan.out_dtype)
    if plan.mode == "faithful":
        bt = _tb.tile_t(block_t, T,
                        sampler_mod.result_dtype(spec, plan.out_dtype))
        n_tiles = -(-T // bt)
        states = _faithful_tile_states(plan, bt, n_tiles, xs0)
        return _tb.block_faithful(roots, plan.h, states, block_t=bt,
                                  block_s=block_s,
                                  interpret=use_interpret(),
                                  sampler=spec, out_dtype=plan.out_dtype)
    raise ValueError(f"unknown mode {plan.mode!r}")


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------

def select_backend(plan: GenPlan) -> str:
    """Pick a backend from the plan shape and the runtime platform.

    On TPU, shapes with at least one VPU tile of work (S >= 128 lanes,
    T >= 8 sublanes) go to the Pallas kernels; everything else — and
    everything off-TPU, where the kernels only run under the interpreter —
    compiles through plain XLA.  ``"ref"`` is never auto-selected; it is
    the oracle, asked for by name.
    """
    T, S = plan.shape
    if jax.default_backend() == "tpu" and S >= 128 and T >= 8:
        return "pallas"
    return "xla"


def _validate_plan(plan: GenPlan) -> None:
    spec = sampler_mod.parse(plan.sampler)          # raises on bad spec
    sampler_mod.result_dtype(spec, plan.out_dtype)  # raises on bad dtype
    if spec[0] == "normal" and plan.num_steps % 2:
        raise ValueError(
            f"sampler='normal' pairs adjacent rows (Box-Muller) and needs "
            f"an even T, got T={plan.num_steps}")


def generate(plan: GenPlan, *, backend: Optional[str] = None,
             block_t: int = DEFAULT_BLOCK_T, block_s: int = DEFAULT_BLOCK_S,
             xs0: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """(T, S) block for ``plan``, time-major; dtype set by the sampler
    stage (uint32 bits by default, float32/bfloat16 for the float
    samplers, bool for bernoulli).

    ``backend`` overrides ``select_backend``; ``xs0`` optionally supplies
    pre-advanced (S, 4) xorshift start states for faithful mode (used by
    ``generate_sharded``, where substream identity follows the GLOBAL
    stream index, not the local shard).

    Example:
        >>> import numpy as np
        >>> from repro.core import engine
        >>> plan = engine.make_plan(seed=7, num_streams=4, num_steps=8)
        >>> blk = engine.generate(plan, backend="xla")
        >>> (blk.shape, str(blk.dtype))
        ((8, 4), 'uint32')
        >>> oracle = engine.generate(plan, backend="ref")
        >>> bool(np.array_equal(np.asarray(blk), np.asarray(oracle)))
        True
    """
    _validate_plan(plan)
    name = backend or select_backend(plan)
    try:
        fn = _BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; have {available_backends()}")
    return fn(plan, block_t=block_t, block_s=block_s, xs0=xs0)


def sample(plan: GenPlan, *, sampler: Optional[str] = None,
           out_dtype: Optional[str] = None, backend: Optional[str] = None,
           block_t: int = DEFAULT_BLOCK_T, block_s: int = DEFAULT_BLOCK_S,
           xs0: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """``generate`` with the sampler stage overridden per call.

    ``sample(plan, sampler="uniform")`` draws U[0,1) floats from the plan's
    (T, S) window without materializing the uint32 bits on any backend
    that fuses (xla fuses elementwise; pallas applies the transform
    in-VMEM).  ``sampler=None`` keeps the plan's own stage.

    Example:
        >>> from repro.core import engine
        >>> plan = engine.make_plan(seed=7, num_streams=4, num_steps=8)
        >>> u = engine.sample(plan, sampler="uniform")
        >>> (u.shape, str(u.dtype))
        ((8, 4), 'float32')
        >>> bool((u >= 0).all()) and bool((u < 1).all())
        True
    """
    if sampler is not None or out_dtype is not None:
        plan = dataclasses.replace(
            plan,
            sampler=plan.sampler if sampler is None else sampler,
            out_dtype=plan.out_dtype if out_dtype is None else out_dtype)
    return generate(plan, backend=backend, block_t=block_t, block_s=block_s,
                    xs0=xs0)


def shift_plan(plan: GenPlan, delta: int) -> GenPlan:
    """The same plan ``delta`` counter steps later (window ``[ctr+delta,
    ctr+delta+T)``).  Static offsets stay static; traced counters get a
    traced add — either way the shifted plan is bit-identical to leasing
    the later window directly.
    """
    delta = int(delta)
    d_hi, d_lo = (u64.to_u32(v) for v in u64.const64(delta))
    return dataclasses.replace(
        plan, ctr=u64.add64(plan.ctr, (d_hi, d_lo)),
        offset=None if plan.offset is None else plan.offset + delta)


def generate_windows(plan: GenPlan, num_windows: int, *,
                     backend: Optional[str] = None,
                     block_t: int = DEFAULT_BLOCK_T,
                     block_s: int = DEFAULT_BLOCK_S) -> jnp.ndarray:
    """(W, T, S) stack of W *consecutive* counter windows of ``plan``.

    Window ``w`` covers counter steps ``[ctr + w*T, ctr + (w+1)*T)`` —
    bit-identical on every backend to stacking W ``generate`` calls on
    ``shift_plan(plan, w*T)``, but dispatched as ONE device program.
    Counter addressing makes W consecutive windows one wide window: row
    ``w*T + t`` of a ``W*T``-row block is step ``t`` of window ``w``, so
    every backend but ``"ref"`` generates the wide block and reshapes
    it.  A tile may straddle a window edge: the ctr kernel is a pure map
    of (row counter, stream), the faithful kernel restarts its chain per
    tile from a state jumped to that tile's own offset, and the sampler
    stages pair rows ``(2k, 2k+1)`` only, with an even T for ``normal``.
    ``"ref"`` is literally the stacked loop (the oracle).

    This is the dispatch-amortization lever: a standing producer that
    fuses W windows per call pays the per-call jit/launch overhead once
    per W blocks (``BlockProducer(fuse=W)``).

    Example:
        >>> import numpy as np
        >>> from repro.core import engine
        >>> plan = engine.make_plan(seed=7, num_streams=4, num_steps=6)
        >>> stack = engine.generate_windows(plan, 3, backend="xla")
        >>> stack.shape                          # (W, T, S)
        (3, 6, 4)
        >>> w2 = engine.generate(engine.shift_plan(plan, 12), backend="xla")
        >>> bool(np.array_equal(np.asarray(stack[2]), np.asarray(w2)))
        True
    """
    _validate_plan(plan)
    W = int(num_windows)
    if W < 1:
        raise ValueError(f"num_windows must be >= 1, got {num_windows}")
    T, S = plan.shape
    name = backend or select_backend(plan)
    if name not in _BACKENDS:
        raise ValueError(
            f"unknown backend {name!r}; have {available_backends()}")
    if name == "ref":
        return jnp.stack([generate(shift_plan(plan, w * T), backend="ref",
                                   block_t=block_t, block_s=block_s)
                          for w in range(W)])
    wide = dataclasses.replace(plan, num_steps=W * T)
    out = generate(wide, backend=name, block_t=block_t, block_s=block_s)
    return out.reshape(W, T, S)


def generate_flat(plan: GenPlan, *, backend: Optional[str] = None,
                  block_t: int = DEFAULT_BLOCK_T,
                  block_s: int = DEFAULT_BLOCK_S) -> jnp.ndarray:
    """(T,) vector for a single-stream plan (S must be 1); dtype follows
    the plan's sampler stage."""
    if plan.num_streams != 1:
        raise ValueError(f"generate_flat needs S=1, got S={plan.num_streams}")
    return generate(plan, backend=backend, block_t=block_t,
                    block_s=block_s)[:, 0]


# ---------------------------------------------------------------------------
# Multi-device fan-out
# ---------------------------------------------------------------------------

def default_mesh(axis_name: str = "streams") -> jax.sharding.Mesh:
    """1-D mesh over every local device, stream axis last."""
    return jax.sharding.Mesh(np.array(jax.devices()), (axis_name,))


def generate_sharded(plan: GenPlan, *, mesh: Optional[jax.sharding.Mesh] = None,
                     axis_name: str = "streams",
                     axis_names: Optional[Tuple[str, ...]] = None,
                     backend: Optional[str] = None,
                     block_t: int = DEFAULT_BLOCK_T,
                     block_s: int = DEFAULT_BLOCK_S) -> jnp.ndarray:
    """(T, S) block computed with the stream axis sharded over ``mesh``.

    The software analogue of the paper's SOU instance scaling: the root
    state (x0, ctr) is replicated — it is two u32 scalars, the paper's
    "one multiplier" — and each device derives its own column slice by
    counter addressing.  No collective appears in the compiled program;
    the result is bit-identical to ``generate`` on one device.

    ``axis_names`` selects an N-D fan-out: the stream axis is sharded
    over the PRODUCT of the named mesh axes (e.g. ``("hosts", "streams")``
    for the 2-D multi-host layout, or a production mesh's
    ``("data", "model")``).  Because the stream axis carries GLOBAL
    column identity — shard (i, j) of an (H, D) grid owns columns
    ``[(i*D + j) * S_loc, ...)`` — the result stays bit-identical to the
    1-D and single-device paths for any mesh factorization.  When
    ``axis_names`` is None the historical 1-D ``axis_name`` is used.

    S is padded up to a multiple of the total device count and sliced
    back.

    Example:
        >>> import numpy as np
        >>> from repro.core import engine
        >>> plan = engine.make_plan(seed=7, num_streams=6, num_steps=8)
        >>> out = engine.generate_sharded(plan)   # default mesh (1 CPU here)
        >>> direct = engine.generate(plan, backend="xla")
        >>> bool(np.array_equal(np.asarray(out), np.asarray(direct)))
        True
    """
    from jax.sharding import PartitionSpec as P

    if axis_names is None:
        axis_names = (axis_name,)
    axes = tuple(axis_names)
    if mesh is None:
        if axes != (axis_name,):
            raise ValueError("axis_names requires an explicit mesh")
        mesh = default_mesh(axis_name)
    for ax in axes:
        if ax not in mesh.axis_names:
            raise ValueError(f"mesh has no axis {ax!r}; has {mesh.axis_names}")
    n_dev = 1
    for ax in axes:
        n_dev *= mesh.shape[ax]
    T, S = plan.shape
    Sp = _pad_to(S, n_dev)

    h_hi = jnp.pad(plan.h[0], (0, Sp - S))
    h_lo = jnp.pad(plan.h[1], (0, Sp - S))
    operands = [h_hi, h_lo]
    in_specs = [P(axes), P(axes)]
    if plan.mode == "faithful":
        # substream identity follows the global stream index: prep the
        # full (Sp, 4) start-state table once, shard it with h.
        padded = dataclasses.replace(plan, h=(h_hi, h_lo))
        xs0 = _faithful_start_states(padded)
        operands.append(xs0)
        in_specs.append(P(axes, None))

    def local(hh, hl, *rest):
        lp = dataclasses.replace(plan, h=(hh, hl))
        lxs0 = rest[0] if rest else None
        return generate(lp, backend=backend, block_t=block_t,
                        block_s=block_s, xs0=lxs0)

    out = jax.shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                        out_specs=P(None, axes), check_vma=False)(*operands)
    return out[:, :S]
