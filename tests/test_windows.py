"""Multi-window fusion: ``engine.generate_windows`` emits W consecutive
counter windows in one dispatch, bit-identical to W stacked ``generate``
calls on every backend, both decorrelator modes, every sampler stage,
and awkward (non-tile-multiple) window lengths — and the pallas path
compiles to exactly ONE pallas_call."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, xorshift

BACKENDS = ("ref", "xla", "pallas")


def _stacked(plan, W, backend="ref"):
    """The oracle: W independent single-window generate calls."""
    T = plan.num_steps
    return np.stack([
        np.asarray(engine.generate(engine.shift_plan(plan, w * T),
                                   backend=backend))
        for w in range(W)])


def _raw(a):
    return a.view(np.uint16) if a.dtype == jnp.bfloat16 else a


# ---------------------------------------------------------------------------
# parity: backend x mode x sampler x awkward window length
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode,deco", [("ctr", "splitmix64"),
                                       ("ctr", "fmix32"),
                                       ("faithful", "splitmix64"),
                                       ("faithful", "fmix32")])
@pytest.mark.parametrize("backend", BACKENDS)
def test_windows_match_stacked_generate(backend, mode, deco):
    T, S, W = 12, 70, 3                 # T far off the 8-row tile multiple
    plan = engine.make_plan(seed=42, num_streams=S, num_steps=T,
                            mode=mode, deco=deco)
    expect = _stacked(plan, W)
    got = np.asarray(engine.generate_windows(plan, W, backend=backend,
                                             block_t=8, block_s=16))
    assert got.shape == (W, T, S)
    assert np.array_equal(got, expect)


@pytest.mark.parametrize("sampler,dtype", [("bits", "float32"),
                                           ("uniform", "float32"),
                                           ("uniform", "bfloat16"),
                                           ("normal", "float32"),
                                           ("normal", "bfloat16"),
                                           ("bernoulli(0.3)", "float32")])
@pytest.mark.parametrize("backend", BACKENDS)
def test_windows_sampler_parity(backend, sampler, dtype):
    T, S, W = 20, 33, 3                 # awkward rows AND lanes
    plan = engine.make_plan(seed=9, num_streams=S, num_steps=T,
                            sampler=sampler, out_dtype=dtype)
    expect = _stacked(plan, W)
    got = np.asarray(engine.generate_windows(plan, W, backend=backend,
                                             block_t=8, block_s=16))
    assert np.array_equal(_raw(got), _raw(expect))


@pytest.mark.parametrize("backend", BACKENDS)
def test_single_window_equals_generate(backend):
    plan = engine.make_plan(seed=5, num_streams=16, num_steps=8)
    got = np.asarray(engine.generate_windows(plan, 1, backend=backend))
    assert np.array_equal(got[0],
                          np.asarray(engine.generate(plan, backend=backend)))


def test_windows_from_nonzero_counter():
    """Windows lease mid-stream exactly like shifted plans do."""
    T, S, W = 12, 24, 4
    plan = engine.make_plan(seed=13, num_streams=S, num_steps=T, offset=37)
    for backend in BACKENDS:
        got = np.asarray(engine.generate_windows(plan, W, backend=backend,
                                                 block_t=8, block_s=16))
        assert np.array_equal(got, _stacked(plan, W))


@pytest.mark.parametrize("mode", ["ctr", "faithful"])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_windows_traced_counter_matches_static(backend, mode):
    """The producer path: counter traced through jit, offset=None.  The
    counter sits just below 2**32, so the stack crosses the low word's
    carry, and 8-row tiles straddle the 12-step window edges."""
    T, S, W = 12, 16, 3
    plan = engine.make_plan(seed=3, num_streams=S, num_steps=T, mode=mode,
                            offset=2**32 - 20)
    traced = dataclasses.replace(plan, offset=None)

    @jax.jit
    def fn(hi, lo):
        p = dataclasses.replace(traced, ctr=(hi, lo))
        return engine.generate_windows(p, W, backend=backend, block_t=8,
                                       block_s=16)

    hi, lo = plan.ctr
    got = np.asarray(fn(jnp.asarray(hi), jnp.asarray(lo)))
    assert np.array_equal(got, _stacked(plan, W))


def test_faithful_tile_states_one_builder():
    """Tile states of a wide window stack: a static offset, the same
    counter traced, and a chain from the plan's own start states all
    equal the host's per-tile jumps (tile i at offset + i * block_t)."""
    T, S, W, bt = 12, 10, 3, 8        # 36 rows: 5 tiles, the last short
    offset = 2**32 + 5
    wide = engine.make_plan(seed=8, num_streams=S, num_steps=W * T,
                            mode="faithful", offset=offset)
    n_tiles = -(-W * T // bt)
    static = np.asarray(engine._faithful_tile_states(wide, bt, n_tiles))

    @jax.jit
    def traced(hi, lo):
        p = dataclasses.replace(wide, ctr=(hi, lo), offset=None)
        return engine._faithful_tile_states(p, bt, n_tiles)

    hi, lo = wide.ctr
    from_ctr = np.asarray(traced(jnp.asarray(hi), jnp.asarray(lo)))
    chained = np.asarray(engine._faithful_tile_states(
        wide, bt, n_tiles, xs0=engine._faithful_start_states(wide)))
    lanes = xorshift.lane_table(S)
    host = np.stack([xorshift.jump_batch(lanes, offset + i * bt).T
                     for i in range(n_tiles)])
    assert static.shape == (n_tiles, 4, S)
    for got in (static, from_ctr, chained):
        assert np.array_equal(got, host)


def test_shift_plan_matches_offset_lease():
    plan = engine.make_plan(seed=21, num_streams=8, num_steps=16)
    direct = engine.make_plan(seed=21, num_streams=8, num_steps=16,
                              offset=48)
    a = np.asarray(engine.generate(engine.shift_plan(plan, 48)))
    assert np.array_equal(a, np.asarray(engine.generate(direct)))


def test_invalid_window_count_raises():
    plan = engine.make_plan(seed=1, num_streams=8, num_steps=8)
    for bad in (0, -2):
        with pytest.raises(ValueError, match="num_windows"):
            engine.generate_windows(plan, bad)
    with pytest.raises(ValueError, match="backend"):
        engine.generate_windows(plan, 2, backend="nope")


# ---------------------------------------------------------------------------
# fusion: the pallas path is ONE kernel launch for all W windows
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["ctr", "faithful"])
def test_pallas_windows_is_one_pallas_call(mode):
    T, S, W = 64, 256, 4
    plan = engine.make_plan(seed=3, num_streams=S, num_steps=T, mode=mode,
                            sampler="uniform")
    jaxpr = jax.make_jaxpr(
        lambda: engine.generate_windows(plan, W, backend="pallas"))()
    calls = [e for e in jaxpr.jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1, [e.primitive.name for e in jaxpr.jaxpr.eqns]
