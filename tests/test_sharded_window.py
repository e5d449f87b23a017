"""``BlockService(mesh=...)`` faithful windows on four host devices.

The window program takes its counter traced, so every window jumps the
xorshift128 substream table in-graph.  On a mesh, each device must get the
columns of its global stream indices, bit for bit as one device and the
plain reference (``bench/reference/faithful.py``) give them, and must jump
only its own rows of the table: the partitioned program's jump loops
carry ``S / devices`` rows, never the whole table.  The tests see one
device (tests/conftest.py), so a subprocess with four runs the checks.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = r"""
import json, os, re, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [{src!r}, {root!r}]
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from repro.core import engine
from repro.runtime import blocks
from bench.reference import ctr, faithful

SEED, LO = 2 ** 31 + 4099, 2 ** 32 + 4099     # a counter above 32 bits
mesh = Mesh(np.array(jax.devices()), ("streams",))
out = {{"devices": len(jax.devices())}}

def window(S, T, **kw):
    svc = blocks.BlockService(seed=SEED, mesh=mesh, **kw)
    svc.open("bench/misrn-faithful", num_streams=S, mode="faithful")
    lease = svc.lease("bench/misrn-faithful", T, at=LO)
    return svc, lease, svc.generate(lease)

# S = 1001: not a multiple of 4 x 512, so the stream axis is padded
svc, lease, blk = window(1001, 24)
got = np.asarray(blk)
one = np.asarray(engine.generate(lease.plan(), backend="xla"))
out["one_device"] = bool(np.array_equal(got, one))
cols = np.arange(1001)
stream = ctr.Stream(SEED, ctr.channel_purpose("bench/misrn-faithful"), cols)
out["reference"] = faithful.mismatches(jnp.asarray(got), LO, stream, cols,
                                       chunk=8)
out["reference_off_by_one"] = faithful.mismatches(
    jnp.asarray(got), LO + 1, stream, cols, chunk=8)

ch = svc.channel("bench/misrn-faithful")
fn = svc._window_fn(ch, 24, "bits", "float32")
text = fn.lower(*svc._ctr_args(LO)).compile().as_text()
out["loop_rows"] = sorted({{int(n) for line in text.splitlines()
                           if " while(" in line
                           for n in re.findall(r"u32\[(\d+),4\]",
                                               line.split(" while(")[0])}})

# the Pallas kernel inside the mesh, five row tiles chained per shard
_, lease, blk = window(130, 40, backend="pallas", block_t=8)
out["pallas_tiles"] = bool(np.array_equal(
    np.asarray(blk),
    np.asarray(engine.generate(lease.plan(), backend="xla"))))

# the window's layout: each device holds its own 256 of 1024 columns,
# also where the generation ends in columns gathered from one shard
def shard_shapes():
    return sorted({{tuple(s.data.shape) for s in
                    window(1024, 8)[2].addressable_shards}})
out["shards"] = shard_shapes()
orig = engine.generate_sharded
engine.generate_sharded = lambda plan, **kw: jnp.tile(
    orig(plan, **kw)[:, :256], (1, 4))
out["shards_from_one"] = shard_shapes()
engine.generate_sharded = orig
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def report():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    code = SCRIPT.format(src=str(ROOT / "src"), root=str(ROOT))
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_a_sharded_faithful_window_equals_one_device_and_the_reference(
        report):
    assert report["devices"] == 4
    assert report["one_device"]
    assert report["reference"] == 0
    assert report["reference_off_by_one"] > 1001 * 24 // 2


def test_each_device_jumps_only_its_own_substreams(report):
    # 1001 streams pad to 1004 over four devices: 251 rows a device
    assert report["loop_rows"] == [251]


def test_the_kernel_chains_its_row_tiles_from_each_shards_jumped_states(
        report):
    assert report["pallas_tiles"]


def test_the_window_leaves_the_program_sharded_by_stream(report):
    assert report["shards"] == [[8, 256]]
    # without the stated layout this window came out replicated: the
    # whole of it, 8 x 1024, on every device
    assert report["shards_from_one"] == [[8, 256]]
