"""xorshift128 decorrelator: step, GF(2) jump-ahead, substream spacing."""
import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import golden, u64, xorshift

words = st.tuples(*[st.integers(min_value=0, max_value=(1 << 32) - 1)] * 4)


def test_step_matches_host(rng):
    states = rng.integers(0, 1 << 32, (128, 4), dtype=np.uint32)
    stepped = np.asarray(xorshift.step(jnp.asarray(states)))
    for i in range(128):
        exp = xorshift.step_words(*(int(w) for w in states[i]))
        assert tuple(int(w) for w in stepped[i]) == exp


def test_step_xyzw_matches_step(rng):
    s = rng.integers(0, 1 << 32, (64, 4), dtype=np.uint32)
    a = np.asarray(xorshift.step(jnp.asarray(s)))
    x, y, z, w = xorshift.step_xyzw(*(jnp.asarray(s[:, i]) for i in range(4)))
    b = np.stack([np.asarray(x), np.asarray(y), np.asarray(z), np.asarray(w)], -1)
    assert np.array_equal(a, b)


@settings(max_examples=20, deadline=None)
@given(words, st.integers(min_value=0, max_value=4000))
def test_jump_matches_sequential(state, n):
    seq = state
    for _ in range(n):
        seq = xorshift.step_words(*seq)
    assert xorshift.jump(state, n) == seq


def test_jump_composes():
    st0 = xorshift.DEFAULT_SEED
    a = xorshift.jump(xorshift.jump(st0, 1 << 20), 1 << 21)
    b = xorshift.jump(st0, (1 << 20) + (1 << 21))
    assert a == b


def test_jump_large_no_collision():
    """Substream starts spaced 2**64 apart must all differ (first 16)."""
    tbl = xorshift.lane_table(16)
    assert len({tuple(r) for r in tbl.tolist()}) == 16


def test_lane_table_matches_substream_state():
    tbl = xorshift.lane_table(4)
    for i in range(4):
        assert tuple(int(w) for w in tbl[i]) == xorshift.substream_state(
            xorshift.DEFAULT_SEED, i)


_M64 = (1 << 64) - 1
# Window multiples, the extremes of the 64-bit count, and a seeded random one.
TRACED_COUNTS = [0, 1, 5, 1000, (1 << 33) + 7, 1 << 63, _M64, 4096, 37 * 4096,
                 4096 * ((1 << 40) + 3),
                 int(np.random.default_rng(20261019).integers(0, 1 << 63)) * 2 + 1]
TILE_STRIDE = 256  # the default row tile (DEFAULT_BLOCK_T)


def _split(n):
    return jnp.uint32(n >> 32), jnp.uint32(n & 0xFFFFFFFF)


def _host_jumped(states, n):
    return [xorshift.jump(tuple(int(w) for w in s), n) for s in states]


@pytest.mark.parametrize("shape", ["(8, 4)", "(1, 4)", "vmap16"])
@pytest.mark.parametrize("n", TRACED_COUNTS)
def test_jump_traced_matches_host(rng, n, shape):
    if shape == "vmap16":
        # Batched under vmap: one table, 16 tile offsets.
        states = rng.integers(0, 1 << 32, (4, 4), dtype=np.uint32)
        tiles = jnp.arange(16, dtype=jnp.uint32)

        def tile(i):
            off = u64.add64(_split(n), u64.mul32_wide(i, jnp.uint32(TILE_STRIDE)))
            return xorshift.jump_traced(jnp.asarray(states), off[0], off[1])

        jumped = np.asarray(jax.vmap(tile)(tiles))
        for i in range(16):
            exp = _host_jumped(states, (n + i * TILE_STRIDE) & _M64)
            assert [tuple(int(w) for w in r) for r in jumped[i]] == exp, (i, n)
        return
    rows = int(shape[1])
    states = rng.integers(0, 1 << 32, (rows, 4), dtype=np.uint32)
    jumped = np.asarray(xorshift.jump_traced(jnp.asarray(states), *_split(n)))
    assert [tuple(int(w) for w in r) for r in jumped] == _host_jumped(states, n)


@pytest.mark.parametrize("n", [0, 1, 37 * 4096, _M64, TRACED_COUNTS[-1]])
def test_jump_matrix_matches_host_power(n):
    """The composed 128x128 matrix alone equals the host's M**n, whose
    column j is the host jump of basis state j."""
    host = np.zeros((xorshift.STATE_BITS, xorshift.STATE_BITS), np.uint8)
    for j in range(xorshift.STATE_BITS):
        col = xorshift._state_to_int(
            xorshift.jump(xorshift._int_to_state(1 << j), n))
        host[:, j] = [(col >> r) & 1 for r in range(xorshift.STATE_BITS)]
    composed = np.asarray(xorshift._jump_matrix_traced(*_split(n)))
    assert np.array_equal(composed.astype(np.uint8), host)


def _all_eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    yield from _all_eqns(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _all_eqns(sub)


def test_jump_traced_one_table_wide_matvec():
    """One popcount over the table's rows and no loop carrying them: the
    jump composes M**n first, instead of 64 table-wide matvecs."""
    rows = 1024
    jaxpr = jax.make_jaxpr(jax.jit(xorshift.jump_traced))(
        jnp.zeros((rows, 4), jnp.uint32), *_split(12345))
    eqns = list(_all_eqns(jaxpr.jaxpr))
    wide = [e for e in eqns if e.primitive.name == "population_count"
            and rows in e.invars[0].aval.shape]
    assert len(wide) == 1, [e.primitive.name for e in eqns]
    # A fori_loop with static bounds traces to scan, a dynamic one to while.
    loops = [e for e in eqns if e.primitive.name in ("while", "scan")
             and any(rows in v.aval.shape for v in e.outvars)]
    assert not loops


def test_xorshift_seq_golden_consistency():
    out = golden.xorshift_seq(xorshift.DEFAULT_SEED, 5)
    s = xorshift.DEFAULT_SEED
    exp = []
    for _ in range(5):
        s = xorshift.step_words(*s)
        exp.append(s[3])
    assert out.tolist() == exp


def test_substream_outputs_differ():
    """First 64 outputs of substreams 0..7 are pairwise distinct sequences."""
    outs = [golden.xorshift_seq(xorshift.substream_state(xorshift.DEFAULT_SEED, i), 64)
            for i in range(8)]
    for i in range(8):
        for j in range(i + 1, 8):
            assert not np.array_equal(outs[i], outs[j])


def test_jump_batch_matches_per_state_jump():
    """Vectorized whole-table GF(2) jump == python-int jump per state."""
    tbl = xorshift.lane_table(9)
    for n in (0, 1, 7, 256, 1 << 20, (1 << 40) + 12345):
        batched = xorshift.jump_batch(tbl, n)
        for s in range(9):
            exp = xorshift.jump(tuple(int(w) for w in tbl[s]), n)
            assert tuple(int(w) for w in batched[s]) == exp, (s, n)


def test_jump_batch_does_not_mutate_input():
    tbl = xorshift.lane_table(4)
    snapshot = tbl.copy()
    xorshift.jump_batch(tbl, 123456)
    assert np.array_equal(tbl, snapshot)
