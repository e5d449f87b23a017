"""Unified engine: backend parity (ref/xla/pallas vs numpy golden) on
awkward shapes, dispatch, leaf-derivation dedup, and shard_map fan-out."""
import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, golden, stream as stream_mod, u64
from repro.kernels import ops

BACKENDS = ("ref", "xla", "pallas")


def _golden_block(seed, num_streams, num_steps, mode, offset=0,
                  purpose=0):
    """(T, S) numpy golden for the family make_plan builds."""
    x0p, h_fam = engine.family_from_seed(seed, purpose)
    x0 = u64.join64(np.asarray(x0p[0]), np.asarray(x0p[1]))
    hh, hl = engine.leaf_table(h_fam, num_streams)
    h = np.array([u64.join64(a, b) for a, b in
                  zip(np.asarray(hh), np.asarray(hl))], dtype=object)
    return golden.thundering_block(x0, h, num_steps, mode=mode,
                                   offset=offset).T  # (T, S)


# ---------------------------------------------------------------------------
# backend parity on awkward shapes (non-multiples of (8, 128), offsets)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("T,S,offset", [
    (10, 4, 0),      # tiny, nothing tile-aligned
    (7, 130, 0),     # S just over one lane tile
    (40, 257, 0),    # both dims awkward
    (12, 36, 37),    # awkward + nonzero offset
    (8, 128, 5),     # tile-exact + offset
])
def test_ctr_backend_matches_golden(backend, T, S, offset):
    plan = engine.make_plan(seed=91, num_streams=S, num_steps=T,
                            offset=offset, mode="ctr")
    out = np.asarray(engine.generate(plan, backend=backend))
    assert out.shape == (T, S) and out.dtype == np.uint32
    assert np.array_equal(out, _golden_block(91, S, T, "ctr", offset))


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("T,S,offset", [
    (10, 4, 0),
    (7, 130, 0),
    (12, 36, 37),
])
def test_faithful_backend_matches_golden(backend, T, S, offset):
    plan = engine.make_plan(seed=93, num_streams=S, num_steps=T,
                            offset=offset, mode="faithful")
    out = np.asarray(engine.generate(plan, backend=backend))
    assert np.array_equal(out, _golden_block(93, S, T, "faithful", offset))


@pytest.mark.parametrize("backend", BACKENDS)
def test_fmix32_deco_backend_parity(backend):
    plan = engine.make_plan(seed=95, num_streams=36, num_steps=12,
                            mode="ctr", deco="fmix32")
    base = np.asarray(engine.generate(plan, backend="ref"))
    assert np.array_equal(np.asarray(engine.generate(plan, backend=backend)),
                          base)


def test_faithful_traced_ctr_matches_static_offset():
    """A plan whose counter is only known at trace time (offset=None, the
    stream-API case) must equal the host-jumped static plan bit-exactly."""
    static = engine.make_plan(seed=97, num_streams=20, num_steps=16,
                              offset=100, mode="faithful")
    ch, cl = (jnp.asarray(v, jnp.uint32) for v in u64.split64(100))
    traced = engine.GenPlan(x0=static.x0, h=static.h, num_steps=16,
                            ctr=(ch, cl), offset=None, mode="faithful")
    for backend in ("ref", "xla", "pallas"):
        assert np.array_equal(
            np.asarray(engine.generate(traced, backend=backend)),
            np.asarray(engine.generate(static, backend=backend))), backend


# ---------------------------------------------------------------------------
# dispatch / registry
# ---------------------------------------------------------------------------

def test_registry_lists_all_backends():
    assert set(BACKENDS) <= set(engine.available_backends())


def test_unknown_backend_raises():
    plan = engine.make_plan(seed=1, num_streams=4, num_steps=8)
    with pytest.raises(ValueError, match="unknown backend"):
        engine.generate(plan, backend="cuda")


def test_select_backend_cpu_is_xla():
    plan = engine.make_plan(seed=1, num_streams=512, num_steps=256)
    assert engine.select_backend(plan) == "xla"  # no TPU in this container


def test_generate_flat_requires_single_stream():
    plan = engine.make_plan(seed=1, num_streams=4, num_steps=8)
    with pytest.raises(ValueError, match="S=1"):
        engine.generate_flat(plan)


# ---------------------------------------------------------------------------
# leaf derivation dedup: one helper behind derive(), h_table() and plans
# ---------------------------------------------------------------------------

def test_h_table_matches_stream_derive():
    """ops.h_table[s] == derive(family, s).h — both are engine.derive_leaf."""
    fam = stream_mod.new_stream(77, 0)
    hh, hl = ops.h_table(77, 16)
    for s in range(16):
        child = stream_mod.derive(fam, s)
        assert u64.join64(np.asarray(hh[s]), np.asarray(hl[s])) == \
            u64.join64(np.asarray(child.h_hi), np.asarray(child.h_lo))


@pytest.mark.parametrize("backend", BACKENDS)
def test_bulk_columns_equal_stream_random_bits(backend):
    """Column s of an engine block == per-stream random_bits with leaf h_s
    (the parity the shared derivation helper guarantees)."""
    T, S = 24, 8
    plan = engine.make_plan(seed=55, num_streams=S, num_steps=T)
    blk = np.asarray(engine.generate(plan, backend=backend))
    fam = stream_mod.new_stream(55, 0)
    for s in (0, 3, 7):
        st = fam._replace(h_hi=plan.h[0][s], h_lo=plan.h[1][s])
        assert np.array_equal(blk[:, s],
                              np.asarray(stream_mod.random_bits(st, (T,))))


def test_generate_flat_equals_random_bits():
    s = stream_mod.advance(stream_mod.new_stream(42, 3), 17)
    plan = engine.plan_for_stream(s, 50)
    flat = np.asarray(engine.generate_flat(plan))
    assert np.array_equal(flat, np.asarray(stream_mod.random_bits(s, (50,))))


# ---------------------------------------------------------------------------
# multi-device fan-out
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["ctr", "faithful"])
def test_generate_sharded_single_device_bitexact(mode):
    """shard_map path on the (1-device) test mesh == plain generate."""
    plan = engine.make_plan(seed=13, num_streams=24, num_steps=16, mode=mode)
    a = np.asarray(engine.generate(plan, backend="xla"))
    b = np.asarray(engine.generate_sharded(plan))
    assert np.array_equal(a, b)


def test_generate_sharded_pads_uneven_streams():
    # S not a multiple of the mesh size still returns exactly (T, S)
    plan = engine.make_plan(seed=15, num_streams=7, num_steps=8)
    out = np.asarray(engine.generate_sharded(plan))
    assert out.shape == (8, 7)
    assert np.array_equal(out, np.asarray(engine.generate(plan,
                                                          backend="xla")))


@pytest.mark.parametrize("mode", ["ctr", "faithful"])
def test_generate_sharded_2d_axes_bitexact(mode):
    """2-D (hosts, streams) fan-out on a (1, 1) mesh == plain generate
    (the real multi-device grid is covered by the 8-device subprocess
    test in test_blocks.py)."""
    mesh = jax.sharding.Mesh(np.array(jax.devices()).reshape(1, 1),
                             ("hosts", "streams"))
    plan = engine.make_plan(seed=17, num_streams=24, num_steps=16, mode=mode)
    a = np.asarray(engine.generate(plan, backend="xla"))
    b = np.asarray(engine.generate_sharded(plan, mesh=mesh,
                                           axis_names=("hosts", "streams")))
    assert np.array_equal(a, b)


def test_generate_sharded_axis_validation():
    plan = engine.make_plan(seed=17, num_streams=8, num_steps=4)
    mesh = jax.sharding.Mesh(np.array(jax.devices()).reshape(1, 1),
                             ("hosts", "streams"))
    with pytest.raises(ValueError, match="no axis"):
        engine.generate_sharded(plan, mesh=mesh, axis_names=("hosts", "bogus"))
    with pytest.raises(ValueError, match="requires an explicit mesh"):
        engine.generate_sharded(plan, axis_names=("hosts", "streams"))


SHARDED_SUBPROCESS = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
import numpy as np
from repro.core import engine

assert len(jax.devices()) == 4
ok = {}
for mode in ("ctr", "faithful"):
    plan = engine.make_plan(seed=29, num_streams=64, num_steps=16, mode=mode)
    single = np.asarray(engine.generate(plan, backend="xla"))
    sharded = np.asarray(engine.generate_sharded(plan))
    ok[mode] = bool(np.array_equal(single, sharded))
# uneven split: 4 devices, 26 streams -> padded to 28, sliced back
plan = engine.make_plan(seed=31, num_streams=26, num_steps=8)
ok["uneven"] = bool(np.array_equal(
    np.asarray(engine.generate(plan, backend="xla")),
    np.asarray(engine.generate_sharded(plan))))
# pallas backend inside the sharded path: faithful mode must consume the
# global-index xs0 states, not rebuild the lane table per shard
plan = engine.make_plan(seed=29, num_streams=64, num_steps=16,
                        mode="faithful")
ok["pallas_faithful"] = bool(np.array_equal(
    np.asarray(engine.generate(plan, backend="xla")),
    np.asarray(engine.generate_sharded(plan, backend="pallas"))))
# several row tiles: each tile's start state is chained from the last
plan = engine.make_plan(seed=29, num_streams=64, num_steps=40,
                        mode="faithful")
ok["pallas_faithful_tiles"] = bool(np.array_equal(
    np.asarray(engine.generate(plan, backend="xla")),
    np.asarray(engine.generate_sharded(plan, backend="pallas", block_t=8))))
# sampler stage rides through the shard_map fan-out (uneven split, bf16)
plan = engine.make_plan(seed=37, num_streams=26, num_steps=16,
                        sampler="uniform", out_dtype="bfloat16")
ok["sampler"] = bool(np.array_equal(
    np.asarray(engine.generate(plan, backend="xla")).view(np.uint16),
    np.asarray(engine.generate_sharded(plan)).view(np.uint16)))
print(json.dumps({"devices": len(jax.devices()), **ok}))
"""


def test_generate_sharded_multi_device_subprocess():
    """Real >= 2 host devices (forced CPU platform): sharded block equals
    the single-device block bit-exactly, zero cross-device communication
    required by construction (counter addressing)."""
    # JAX_PLATFORMS=cpu: without it, an installed libtpu spends minutes
    # retrying GCP metadata fetches before falling back to CPU.
    out = subprocess.run([sys.executable, "-c", SHARDED_SUBPROCESS],
                         capture_output=True, text=True, timeout=300,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    rep = json.loads(out.stdout.strip().splitlines()[-1])
    assert rep["devices"] == 4
    assert rep["ctr"] and rep["faithful"] and rep["uneven"]
    assert rep["pallas_faithful"] and rep["pallas_faithful_tiles"]
    assert rep["sampler"]
