"""Sampler output stages: distribution shaping fused into generation.

ThundeRiNG's applications never spill raw random words off-chip — bits
flow through an on-chip FIFO straight into the consumer (Table 7).  The
software analogue: a ``GenPlan`` carries a *sampler* output stage and the
backends apply it where the bits live —

  * ``"ref"`` / ``"xla"``  as fused elementwise jnp on the bit block,
  * ``"pallas"``           in-VMEM inside the generation kernel, so the
                           (T, S) uint32 block never reaches HBM and a
                           bfloat16 output halves bytes/sample.

This module is the single home of the transforms, shared by all three
backends (and the fused Monte-Carlo kernels), which is what makes the
fused outputs bit/value-exact across backends: every path applies the
same jnp ops to the same bits.

Samplers (``GenPlan.sampler`` spec strings):

  "bits"          raw uint32 (default; ``out_dtype`` ignored)
  "uniform"       U[0, 1) from the top 24 bits, float32 or bfloat16
  "normal"        standard normal via Box-Muller over *adjacent row
                  pairs*: rows (2k, 2k+1) of the block supply (u1, u2)
                  and receive (r cos th, r sin th).  Requires even T.
                  u1 is clamped to the smallest positive normal float32,
                  so log(0) can never occur (open-interval guarantee).
  "bernoulli(p)"  bool mask, P(True) = p via the exact host-int
                  threshold round(p * 2**32) (the PR-1 precision rule:
                  p <= 0 / p >= 1 short-circuit to constant masks, the
                  threshold never wraps uint32).

Distribution stages (this PR's programmable-statistics layer — the
software answer to hardware programmable-PRNG statistics):

  "exponential(r)"    Exp(rate r) by inversion, -log(1 - u) / r.
                      1 - u >= 2**-24 > 0, so log(0) is impossible.
  "poisson(r)"        Poisson(rate r), 0 <= r <= POISSON_MAX_RATE, by
                      exact-threshold inversion: the float64 CDF is
                      rounded once to a float32 threshold ladder on the
                      host and the count is the number of thresholds at
                      or below u — one compare+add per ladder rung, no
                      transcendentals at runtime, bit-exact everywhere.
  "gumbel"            standard Gumbel by double-log inversion,
                      -log(-log(u)) with u clamped to TINY_F32, so both
                      logs see strictly positive arguments.  This is the
                      gumbel-max trick's perturbation: adding a gumbel
                      block to logits and taking the argmax samples the
                      softmax — the inference tier's in-kernel
                      bits-to-token stage (``repro.inference``).
  "gamma(k)"          Gamma(shape k >= 1, scale 1) via Marsaglia-Tsang:
                      each element gets GAMMA_RETRY_ROWS candidate
                      (normal, acceptance-uniform) draws derived from
                      its own word by salted fmix32 remixing (the
                      bounded retry-row scheme); the squeeze resolves
                      rejection in-kernel and the first accepted
                      candidate wins.  P(all rejected) < 0.05**6.
                      k == 1 short-circuits to the exact Exp(1) path.
  "gamma(k,theta)"    two-parameter sugar: the gamma(k) stage scaled by
                      theta > 0 — one extra multiply against a host-
                      rounded f32 constant, the final op of the stage
                      (it feeds no add, so no fma_guard is needed).
  "categorical[...]"  draw from weights "categorical[w0,w1,...]" via a
                      packed Walker/Vose alias table: bin = floor(u*K),
                      flip u' < thresh[bin] picks bin or alias[bin].
                      The (thresh, alias) pairs are compile-time f32
                      constants, so the table lives in VMEM with the
                      kernel and the selection is an unrolled K-way
                      where-chain (gather-free, Mosaic-safe).

Counts and category indices are emitted as float32/bfloat16 (lane-width
match with the other stages; exact integers well below 2**24).

Everything here is pure jnp over uint32/float32 and lowers both in
regular jitted JAX and inside Pallas kernel bodies; kernel callers pass
``roll=pltpu.roll`` so the pairing shuffle stays a Mosaic-native
sublane rotate.  The distribution stages are elementwise (no pairing),
so they compose with any tiling.
"""
from __future__ import annotations

import re
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lcg, splitmix, u64
from repro.core.u64 import U32, U64Pair

# Smallest positive normal float32: sqrt(-2 ln TINY) ~ 13.2, finite.
TINY_F32 = np.float32(1.1754944e-38)
TWO_PI_F32 = np.float32(2.0 * np.pi)

# Param slot: None (bits/uniform/normal/gumbel), a float (bernoulli/
# exponential/poisson/gamma) or a tuple of floats (categorical weights;
# gamma's two-parameter (shape, scale) form).  Always hashable — specs
# key functools.partial kernels and jit caches.
SamplerSpec = Tuple[str, Optional[object]]

#: The full sampler spec grammar, quoted verbatim by parse() errors.
SPEC_GRAMMAR = (
    "'bits' | 'uniform' | 'normal' | 'gumbel' | 'bernoulli(p)' | "
    "'exponential(rate)' | 'poisson(rate)' | 'gamma(shape[,scale])' "
    "| 'categorical[w0,w1,...]'")

_SCALAR_RE = re.compile(
    r"^(bernoulli|exponential|poisson|gamma)\(([^)]*)\)$")
_CATEGORICAL_RE = re.compile(r"^categorical\[([^\]]*)\]$")
FLOAT_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}

#: Inversion ladder cap: poisson(rate) must keep rate <= this so the
#: unrolled threshold ladder stays a bounded compile-time constant.
POISSON_MAX_RATE = 32.0
#: Bounded Marsaglia-Tsang retries per element; P(no accept) < 0.05**6.
GAMMA_RETRY_ROWS = 6
#: Alias tables are unrolled K-way where-chains; keep K bounded.
CATEGORICAL_MAX_OUTCOMES = 64


def _parse_float(kind: str, text: str) -> float:
    try:
        value = float(text)
    except ValueError:
        raise ValueError(
            f"unknown sampler parameter {text!r} for {kind}; "
            f"grammar: {SPEC_GRAMMAR}") from None
    if not np.isfinite(value):
        raise ValueError(f"{kind} parameter must be finite, got {text!r}")
    return value


def parse(spec: str) -> SamplerSpec:
    """Sampler spec string -> (kind, param) tuple.

    The param slot is ``None``, a float, or (categorical) a tuple of
    weights, so every parsed spec is hashable and can key jit caches.

    >>> parse("poisson(3.5)")
    ('poisson', 3.5)
    >>> parse("categorical[1, 1, 2]")
    ('categorical', (1.0, 1.0, 2.0))
    >>> parse("gamma(2.5, 0.5)")
    ('gamma', (2.5, 0.5))
    >>> parse("gamma")                 # doctest: +IGNORE_EXCEPTION_DETAIL
    Traceback (most recent call last):
    ValueError: unknown sampler 'gamma'; grammar: ...
    """
    if spec in ("bits", "uniform", "normal", "gumbel"):
        return (spec, None)
    m = _SCALAR_RE.match(spec)
    if m and m.group(1) == "gamma" and "," in m.group(2):
        k_text, _, th_text = m.group(2).partition(",")
        k = _parse_float("gamma", k_text.strip())
        theta = _parse_float("gamma", th_text.strip())
        if k < 1.0:
            raise ValueError(
                f"gamma shape must be >= 1 (Marsaglia-Tsang squeeze "
                f"needs no boost draw), got {k!r}")
        if theta <= 0.0:
            raise ValueError(f"gamma scale must be > 0, got {theta!r}")
        return ("gamma", (k, theta))
    if m:
        kind, p = m.group(1), _parse_float(m.group(1), m.group(2))
        if kind == "exponential" and p <= 0.0:
            raise ValueError(f"exponential rate must be > 0, got {p!r}")
        if kind == "poisson" and not 0.0 <= p <= POISSON_MAX_RATE:
            raise ValueError(f"poisson rate must be in [0, "
                             f"{POISSON_MAX_RATE!r}], got {p!r}")
        if kind == "gamma" and p < 1.0:
            raise ValueError(
                f"gamma shape must be >= 1 (Marsaglia-Tsang squeeze "
                f"needs no boost draw), got {p!r}")
        return (kind, p)
    m = _CATEGORICAL_RE.match(spec)
    if m:
        parts = [s.strip() for s in m.group(1).split(",") if s.strip()]
        weights = tuple(_parse_float("categorical", s) for s in parts)
        if not 1 <= len(weights) <= CATEGORICAL_MAX_OUTCOMES:
            raise ValueError(
                f"categorical needs 1..{CATEGORICAL_MAX_OUTCOMES} "
                f"weights, got {len(weights)}; grammar: {SPEC_GRAMMAR}")
        if min(weights) < 0.0 or sum(weights) <= 0.0:
            raise ValueError(
                f"categorical weights must be >= 0 with positive sum, "
                f"got {weights!r}")
        return ("categorical", weights)
    raise ValueError(f"unknown sampler {spec!r}; grammar: {SPEC_GRAMMAR}")


#: Spec kinds whose outputs are float-coded (see result_dtype).
DISTRIBUTION_KINDS = ("exponential", "poisson", "gamma", "categorical",
                      "gumbel")


def result_dtype(spec: SamplerSpec, out_dtype: str = "float32"):
    """The jnp dtype a sampler stage emits.

    >>> result_dtype(parse("poisson(2.0)"), "bfloat16") == jnp.bfloat16
    True
    """
    kind, _ = spec
    if kind == "bits":
        return jnp.uint32
    if kind == "bernoulli":
        return jnp.bool_
    try:
        return FLOAT_DTYPES[out_dtype]
    except KeyError:
        raise ValueError(f"unknown out_dtype {out_dtype!r}; "
                         f"have {sorted(FLOAT_DTYPES)}")


def bernoulli_threshold(p: float) -> int:
    """Exact uint32 threshold for P(bits < thresh) = p.

    Host-int arithmetic (float32 would wrap or lose low bits near p=1),
    clamped to 2**32 - 1; callers must short-circuit p <= 0 / p >= 1.
    """
    return min(int(round(float(p) * (1 << 32))), (1 << 32) - 1)


# ---------------------------------------------------------------------------
# Generation stage (shared by the ctr-mode kernels)
# ---------------------------------------------------------------------------

def ctr_bits(root: U64Pair, ctr: U64Pair, h: U64Pair,
             deco: str = "splitmix64") -> jnp.ndarray:
    """ThundeRiNG ctr-mode bits: XSH_RR(root + h) ^ deco(h, ctr).

    Operands broadcast, so (BT, 1) roots/counters against (1, BS) leaf
    offsets yield a (BT, BS) tile — the kernel-body form — while (T,)
    against scalars yields the flat form.
    """
    leaf = u64.add64(root, h)
    perm = lcg.xsh_rr(leaf)
    deco_fn = splitmix.ctr_decorrelator if deco == "splitmix64" \
        else splitmix.ctr_decorrelator32
    return perm ^ deco_fn(h, ctr)


# ---------------------------------------------------------------------------
# Output-stage transforms
# ---------------------------------------------------------------------------

def uniform_from_bits(bits: jnp.ndarray, dtype=jnp.float32) -> jnp.ndarray:
    """U[0, 1) from the top 24 bits (matches stream.uniform exactly).

    Always computed at float32 resolution; bfloat16 output is the f32
    value rounded once at the end (the bandwidth-halving cast).  The
    24-bit value converts through int32 (exact, it is < 2**24): Mosaic
    has no direct uint32 -> float32 cast.
    """
    u = ((bits >> U32(8)).astype(jnp.int32).astype(jnp.float32)
         * np.float32(2.0 ** -24))
    return u if dtype == jnp.float32 else u.astype(dtype)


def box_muller(u1: jnp.ndarray, u2: jnp.ndarray) -> jnp.ndarray:
    """Standard normal from two U[0,1) arrays (cos branch), log(0)-safe."""
    r = jnp.sqrt(np.float32(-2.0) * jnp.log(jnp.maximum(u1, TINY_F32)))
    return r * jnp.cos(TWO_PI_F32 * u2)


def normal_pairs(u: jnp.ndarray, roll: Callable = jnp.roll,
                 barrier: bool = False) -> jnp.ndarray:
    """(T, S) standard normals from (T, S) uniforms, T even.

    Box-Muller over adjacent row pairs: rows (2k, 2k+1) supply (u1, u2)
    and receive (r cos th, r sin th) — both branches, so the output shape
    equals the input shape and no bits are wasted.  Pairing is by row
    parity, so any even-aligned tiling (Pallas bt is a multiple of 8)
    computes identical values; kernel bodies pass ``roll=pltpu.roll``.

    ``barrier=True`` pins ``u`` behind an optimization barrier (a value
    identity): without it XLA:CPU rematerializes the whole generation
    pipeline into each roll consumer's fusion, tripling the work.  The
    Pallas kernel does not need it (the tile is computed once in VMEM).
    """
    if barrier:
        u = jax.lax.optimization_barrier(u)
    even = (jax.lax.broadcasted_iota(jnp.uint32, u.shape, 0)
            & U32(1)) == U32(0)
    # up-shift expressed as a positive roll (pltpu.roll rejects negatives)
    mate = jnp.where(even, roll(u, u.shape[0] - 1, 0), roll(u, 1, 0))
    u1 = jnp.where(even, u, mate)
    u2 = jnp.where(even, mate, u)
    r = jnp.sqrt(np.float32(-2.0) * jnp.log(jnp.maximum(u1, TINY_F32)))
    theta = TWO_PI_F32 * u2
    return r * jnp.where(even, jnp.cos(theta), jnp.sin(theta))


def remix_bits(bits: jnp.ndarray, salt: int) -> jnp.ndarray:
    """Derived word stream #salt from a bit block: fmix32 of a
    golden-ratio-salted copy.

    This is the retry-row primitive: a distribution stage that needs
    more than one uniform per element (gamma candidates, the alias-table
    flip) remixes the element's *own* word instead of widening the
    generator footprint, so shaped outputs stay counter-addressable and
    one-word-per-sample on every backend.
    """
    return splitmix.fmix32(bits + U32((salt * 0x9E3779B9) & 0xFFFFFFFF))


def exponential_from_bits(bits: jnp.ndarray, rate: float) -> jnp.ndarray:
    """Exp(rate) float32 by inversion: x = -log(1 - u) / rate.

    ``1 - u`` is at least 2**-24, so the log argument is strictly
    positive (open-interval guarantee without clamping).  The division
    is a compile-time reciprocal, f32-rounded once on the host so all
    backends multiply by the identical constant.
    """
    u = uniform_from_bits(bits)
    return -jnp.log(np.float32(1.0) - u) * np.float32(1.0 / float(rate))


def gumbel_from_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """Standard Gumbel float32 by double-log inversion, -log(-log(u)).

    ``u`` is clamped to the smallest positive normal float32 before the
    inner log (so it is finite) and the largest representable uniform is
    1 - 2**-24 (so the inner log is strictly negative and the outer log
    sees a positive argument): both logs are open-interval safe without
    the ad-hoc ``+ 1e-20`` epsilons of naive implementations.  The range
    is [-log(log(2**24)), log(-log(TINY_F32))] ~ [-2.81, 4.47] on the
    low side and ~16.6 at u -> TINY, all finite.  No products feed adds,
    so the transform needs no fma_guard and is bit-identical across
    batch shapes on a backend.
    """
    u = uniform_from_bits(bits)
    return -jnp.log(-jnp.log(jnp.maximum(u, TINY_F32)))


def poisson_thresholds(rate: float) -> Tuple[float, ...]:
    """Float32 CDF threshold ladder for exact-inversion Poisson(rate).

    Entry j is the float64 CDF F(j) rounded once to float32; the sampled
    count is ``sum_j [u >= F(j)]``.  The ladder stops at the first entry
    that exceeds the largest representable uniform (1 - 2**-24), past
    which no u can reach, so truncation is exact rather than approximate.

    >>> poisson_thresholds(0.0)
    ()
    >>> len(poisson_thresholds(3.5))
    18
    """
    rate = float(rate)
    if not 0.0 <= rate <= POISSON_MAX_RATE:
        raise ValueError(f"poisson rate must be in [0, {POISSON_MAX_RATE!r}]"
                         f", got {rate!r}")
    u_max = 1.0 - 2.0 ** -24
    out, pmf, cdf = [], np.exp(-rate), 0.0
    for j in range(4096):
        cdf += pmf
        t = float(np.float32(cdf))
        if t > u_max:
            break
        out.append(t)
        pmf *= rate / (j + 1)
    return tuple(out)


# Any finite float32 exceeds this, so jnp.maximum(x, _GUARD_FLOOR) is a
# value identity — but the max survives to codegen as a compare+select,
# which pins the rounded product before it reaches an add.  See
# fma_guard.
_GUARD_FLOOR = np.float32(-1e30)


def fma_guard(x: jnp.ndarray) -> jnp.ndarray:
    """Value-identity that blocks FMA contraction of a product.

    XLA:CPU compiles ``a*b + c`` to a fused multiply-add *shape-
    dependently* (the vectorized loop body contracts, the scalar tail
    may not), so the same elementwise graph can yield ULP-different
    bytes at different batch shapes — fatal for journal replay
    (``repro.service.audit``), which regenerates responses through
    differently-shaped executables, and for cross-backend bit-exactness
    (the Pallas interpreter executes op-by-op, uncontracted).
    ``optimization_barrier`` and bitcast round-trips do NOT stop the
    contraction; a ``maximum`` against a huge negative constant does —
    compares and selects are never contraction fodder — at the cost of
    one vector op.  Wrap any product that feeds an add or subtract on a
    bit-reproducibility-critical path:  ``1 + fma_guard(c * z)``.
    (Exact products — powers of two like ``0.5 * zz`` — never need the
    guard: contracting an exact product cannot change the sum.)
    """
    return jnp.maximum(x, _GUARD_FLOOR)


def gamma_mt_constants(shape: float) -> Tuple[float, float]:
    """Marsaglia-Tsang (d, c) for Gamma(shape >= 1): d = k - 1/3,
    c = 1/sqrt(9 d) (the candidate is v = 1 + c z), each rounded once
    to float32 on the host so all backends use identical constants."""
    d = float(shape) - 1.0 / 3.0
    return (float(np.float32(d)),
            float(np.float32(1.0 / np.sqrt(9.0 * d))))


def gamma_from_bits(bits: jnp.ndarray, shape: float) -> jnp.ndarray:
    """Gamma(shape >= 1, scale 1) float32 via Marsaglia-Tsang with
    bounded retry rows.

    Candidate r derives (u1, u2, u_accept) from remix_bits(bits, 3r..),
    z = box_muller(u1, u2), v = (1 + c z)**3; accept if v > 0 and the
    squeeze 1 - u > 0.0331 z**4 or log u - z**2/2 < d(1 - v**3 + 3 log v).
    The first accepting candidate wins; if all GAMMA_RETRY_ROWS reject
    (probability < 0.05**GAMMA_RETRY_ROWS) the element falls back to the
    central value d (z = 0).  Everything is elementwise, so unlike the
    "normal" stage there is no row pairing and no even-T requirement.

    Bit-reproducibility: the two products that feed adds (``c*z`` and
    ``v**3``) are pinned with ``fma_guard``; every other float op is a
    pure product feeding a compare/select, an exact power-of-two
    product, an add-chain, or a transcendental call — none of which
    XLA can contract.  The transform is therefore bit-identical across
    batch shapes and jit/eager on a given backend (what journal replay
    needs), and across ref/xla everywhere; the pallas interpreter's
    tile padding can shift ``log`` onto a different libm SIMD lane at
    some shapes, giving the same few-ULP slack as the "normal" stage.
    """
    d32, c32 = gamma_mt_constants(shape)
    d, c = np.float32(d32), np.float32(c32)
    out = jnp.full(bits.shape, d, jnp.float32)
    for r in reversed(range(GAMMA_RETRY_ROWS)):
        u1 = uniform_from_bits(remix_bits(bits, 3 * r + 1))
        u2 = uniform_from_bits(remix_bits(bits, 3 * r + 2))
        ua = uniform_from_bits(remix_bits(bits, 3 * r + 3))
        z = box_muller(u1, u2)
        v = np.float32(1.0) + fma_guard(c * z)
        lv = jnp.log(jnp.maximum(v, TINY_F32))
        lv3 = (lv + lv) + lv                    # 3 log v, mul-free
        v3 = v * v * v
        zz = z * z
        squeeze = (np.float32(1.0) - ua) > np.float32(0.0331) * zz * zz
        log_ok = (jnp.log(jnp.maximum(ua, TINY_F32))
                  - np.float32(0.5) * zz) < (
            d * ((np.float32(1.0) - fma_guard(v3)) + lv3))
        accept = (v > np.float32(0.0)) & (squeeze | log_ok)
        out = jnp.where(accept, d * v3, out)
    return out


def alias_table(weights: Tuple[float, ...]) -> Tuple[Tuple[float, int], ...]:
    """Walker/Vose alias table for categorical weights.

    Returns K packed (threshold, alias) pairs: bin j keeps its own index
    with probability ``threshold[j]`` and defers to ``alias[j]``
    otherwise.  Thresholds are float64-constructed then f32-rounded once,
    so every backend compares against identical constants.

    >>> alias_table((1.0,))
    ((1.0, 0),)
    >>> [(round(t, 4), a) for t, a in alias_table((0.5, 0.25, 0.25))]
    [(1.0, 0), (0.75, 0), (0.75, 0)]
    """
    total = float(sum(weights))
    k = len(weights)
    scaled = [w / total * k for w in weights]
    thresh, alias = [0.0] * k, [0] * k
    small = [j for j in range(k) if scaled[j] < 1.0]
    large = [j for j in range(k) if scaled[j] >= 1.0]
    while small and large:
        s, g = small.pop(), large.pop()
        thresh[s], alias[s] = scaled[s], g
        scaled[g] = (scaled[g] + scaled[s]) - 1.0
        (small if scaled[g] < 1.0 else large).append(g)
    for j in large + small:   # numerical leftovers: certainly themselves
        thresh[j], alias[j] = 1.0, j
    return tuple((float(np.float32(t)), a) for t, a in zip(thresh, alias))


def categorical_from_bits(bits: jnp.ndarray,
                          weights: Tuple[float, ...]) -> jnp.ndarray:
    """Category index (float32-coded) from a packed alias table.

    bin = floor(u K) never reaches K: the largest uniform is 1 - 2**-24,
    and K(1 - 2**-24) rounds below K for every K <= 64 (exactly K - K/2**24
    when K is a power of two, and more than half a ULP below K otherwise).
    The flip uniform comes from remix_bits so it is independent of the
    bin-selector bits.  Selection is an unrolled, gather-free where-chain
    over compile-time constants — the packed table rides in VMEM with the
    kernel body.
    """
    table = alias_table(weights)
    k = len(table)
    if k == 1:
        return jnp.zeros(bits.shape, jnp.float32)
    bin_f = jnp.floor(uniform_from_bits(bits) * np.float32(k))
    flip = uniform_from_bits(remix_bits(bits, 0))
    out = jnp.zeros(bits.shape, jnp.float32)
    for j, (t, a) in enumerate(table):
        pick = jnp.where(flip < np.float32(t), np.float32(j), np.float32(a))
        out = jnp.where(bin_f == np.float32(j), pick, out)
    return out


def apply(bits: jnp.ndarray, spec: SamplerSpec, out_dtype: str = "float32",
          roll: Callable = jnp.roll, barrier: bool = False) -> jnp.ndarray:
    """Apply a parsed sampler stage to a uint32 bit block.

    The ONE transform every backend runs — outside the kernel for
    ref/xla, inside VMEM for pallas (with ``roll=pltpu.roll``).

    >>> import numpy as np
    >>> bits = (jnp.arange(8, dtype=jnp.uint32).reshape(2, 4)
    ...         * jnp.uint32(0x9E3779B9))
    >>> x = apply(bits, parse("poisson(3.5)"))
    >>> x.dtype, bool((x >= 0).all())
    (dtype('float32'), True)
    """
    kind, p = spec
    if kind == "bits":
        return bits
    if kind == "uniform":
        return uniform_from_bits(bits, result_dtype(spec, out_dtype))
    if kind == "normal":
        z = normal_pairs(uniform_from_bits(bits), roll=roll,
                         barrier=barrier)
        dtype = result_dtype(spec, out_dtype)
        return z if dtype == jnp.float32 else z.astype(dtype)
    if kind == "bernoulli":
        if p <= 0.0:
            return jnp.zeros(bits.shape, jnp.bool_)
        if p >= 1.0:
            return jnp.ones(bits.shape, jnp.bool_)
        return bits < U32(bernoulli_threshold(p))
    if kind in DISTRIBUTION_KINDS:
        if kind == "exponential":
            x = exponential_from_bits(bits, p)
        elif kind == "poisson":
            u = uniform_from_bits(bits)
            x = jnp.zeros(bits.shape, jnp.float32)
            for t in poisson_thresholds(p):
                x = x + (u >= np.float32(t)).astype(jnp.float32)
        elif kind == "gamma":
            shape, scale = p if isinstance(p, tuple) else (p, None)
            x = exponential_from_bits(bits, 1.0) if shape == 1.0 \
                else gamma_from_bits(bits, shape)
            if scale is not None and scale != 1.0:
                # pure scale multiply: the stage's final op, feeding no
                # add — contraction-safe without a guard
                x = x * np.float32(scale)
        elif kind == "gumbel":
            x = gumbel_from_bits(bits)
        else:
            x = categorical_from_bits(bits, p)
        dtype = result_dtype(spec, out_dtype)
        return x if dtype == jnp.float32 else x.astype(dtype)
    raise ValueError(f"unknown sampler kind {kind!r}")


def sublane_multiple(dtype) -> int:
    """Minimum sublane tile multiple for a Pallas out dtype (TPU tiling)."""
    if dtype == jnp.bfloat16:
        return 16
    if dtype in (jnp.bool_, jnp.int8, jnp.uint8):
        return 32
    return 8
