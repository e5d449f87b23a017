"""Dry-run machinery tests.

The full 512-device dry-run is exercised via ``python -m
repro.launch.dryrun`` (EXPERIMENTS.md §Dry-run); here we unit-test the
pieces: HLO collective parsing, pspec resolution, mesh construction, and
a tiny end-to-end lower+compile on a subprocess-forced 8-device host
platform (keeping THIS process at 1 device).
"""
import json
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.analysis import (_DTYPE_BYTES, _shape_bytes,
                                   collective_bytes)
from repro.launch.mesh import make_mesh_auto
from repro.models import sharding


def test_shape_bytes():
    assert _shape_bytes("f32[2,3]") == 24
    assert _shape_bytes("bf16[128]") == 256
    assert _shape_bytes("(f32[2], u32[4])") == 24
    assert _shape_bytes("pred[8]") == 8
    assert _shape_bytes("f32[]") == 4


def test_collective_bytes_parsing():
    hlo = textwrap.dedent("""\
        %ag = f32[64,128] all-gather(%x), replica_groups={}
        %ar.1 = bf16[32] all-reduce(%y), to_apply=%add
        %ars = bf16[32] all-reduce-start(%y)
        %ard = bf16[32] all-reduce-done(%ars)
        %rs = f32[16] reduce-scatter(%z)
        %cp = u32[8,8] collective-permute(%w)
        %dot = f32[9999] dot(%a, %b)
    """)
    got = collective_bytes(hlo)
    assert got["all-gather"] == 64 * 128 * 4
    assert got["all-reduce"] == 64 + 64   # plain + start (done skipped)
    assert got["reduce-scatter"] == 64
    assert got["collective-permute"] == 256
    assert got["total"] == sum(got[k] for k in (
        "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
        "collective-permute"))


@pytest.fixture(scope="module")
def mesh44():
    return make_mesh_auto((1, 1), ("data", "model"))


def test_param_pspec_tp_priority(mesh44):
    # kv_heads divisible -> model on kv; FSDP puts embed on data
    spec = sharding.param_pspec(("embed", "kv_heads", "q_rep", "head"),
                                (64, 1, 4, 16), mesh44)
    assert spec == P("data", "model", None, None)


def test_param_pspec_vocab_tables_tp_only():
    mesh = make_mesh_auto((1, 1), ("data", "model"))
    spec = sharding.param_pspec(("vocab", "embed"), (1024, 64), mesh,
                                mode="train")
    assert spec == P("model", None)  # no FSDP on table d_model


def test_cache_pspec_mqa_falls_back_to_ctx():
    mesh = make_mesh_auto((1, 1), ("data", "model"))
    # kv=1 not divisible by model>1 would shard ctx; with model=1 all fine
    spec = sharding._cache_kv_pspec(mesh, (4, 8, 128, 1, 64), kv_idx=3,
                                    ctx_idx=2)
    assert spec[3] == "model"


SUBPROCESS_PROG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config, input_specs, SHAPES
from repro.launch import steps as steps_mod
from repro.launch import analysis as dr
from repro.models import registry
from repro.optim import adamw_init

from repro.launch.mesh import make_mesh_auto
mesh = make_mesh_auto((4, 2), ("data", "model"))
cfg = get_config("glm4_9b").scaled(
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab=256,
    q_chunk=16, loss_chunks=2)
model = registry.build(cfg)
holder = {}
def initf():
    p, s = model.init(0)
    holder["specs"] = s
    return p
params = jax.eval_shape(initf)
pshard, _ = steps_mod.param_sharding_tree(model, params, holder["specs"],
                                          mesh, "train")
batch = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
         "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
bshard = steps_mod.batch_sharding(cfg, batch, mesh)
opt = jax.eval_shape(adamw_init, params)
oshard = steps_mod.opt_sharding_like(pshard, mesh)
ts = steps_mod.make_train_step(model, microbatches=2)
with jax.set_mesh(mesh):
    lowered = jax.jit(ts, in_shardings=(pshard, oshard, bshard,
                                        NamedSharding(mesh, P())),
                      out_shardings=(pshard, oshard, None)).lower(
        params, opt, batch, jax.ShapeDtypeStruct((), jnp.int32))
    compiled = lowered.compile()
ma = compiled.memory_analysis()
coll = dr.collective_bytes(compiled.as_text())
print(json.dumps({"devices": len(jax.devices()),
                  "temp": ma.temp_size_in_bytes,
                  "coll_total": coll["total"]}))
"""


@pytest.mark.slow
def test_end_to_end_dryrun_small_mesh():
    """Real lower+compile on an 8-device forced host platform, with the
    production sharding machinery, in a subprocess."""
    # JAX_PLATFORMS=cpu: without it, an installed libtpu spends minutes
    # retrying GCP metadata fetches before falling back to CPU.
    out = subprocess.run([sys.executable, "-c", SUBPROCESS_PROG],
                         capture_output=True, text=True, timeout=600,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    rep = json.loads(out.stdout.strip().splitlines()[-1])
    assert rep["devices"] == 8
    assert rep["coll_total"] > 0   # FSDP/TP emitted real collectives


def test_this_process_sees_one_device():
    assert len(jax.devices()) == 1
