"""Ahead-of-time compiles of the main-path Pallas kernels for a v5e chip.

Each test lowers one kernel wrapper at real width with ``interpret=False``
and compiles it for a described (not attached) ``v5e:2x2`` topology, so
the TPU compiler's tiling and memory checks run here without a chip.
``engine.generate`` would take its CPU branch here, so the wrappers are
called directly; the window-stack test, which has no wrapper of its own,
patches ``engine.use_interpret`` instead.  Every compile must carry a
``tpu_custom_call`` (the kernel really lowered through Mosaic).

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports this file.
"""
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import engine, sampler
from repro.inference.kernels import gumbel_argmax
from repro.kernels import fused_dropout, mc, thundering_block as tb

U32 = jnp.uint32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(text):
    assert "tpu_custom_call" in text


S_BULK, T_BULK = 65_536, 1_024


@pytest.mark.parametrize("spec,out_dtype", [
    ("bits", "float32"), ("uniform", "bfloat16"), ("normal", "float32"),
    ("gamma(2.5)", "float32")])
def test_block_ctr_compiles(one_chip, spec, out_dtype):
    def fn(r0, r1, c0, c1, h0, h1):
        return tb.block_ctr((r0, r1), (c0, c1), (h0, h1),
                            sampler=sampler.parse(spec), out_dtype=out_dtype,
                            interpret=False)
    col, row = ((T_BULK,), U32), ((S_BULK,), U32)
    _assert_kernel(_compile_text(fn, one_chip, col, col, col, col, row, row))


@pytest.mark.parametrize("spec", ["bits", "normal"])
def test_block_faithful_compiles(one_chip, spec):
    parsed = sampler.parse(spec)
    bt = tb.tile_t(tb.DEFAULT_BLOCK_T, T_BULK,
                   sampler.result_dtype(parsed, "float32"))

    def fn(r0, r1, h0, h1, xs):
        return tb.block_faithful((r0, r1), (h0, h1), xs, block_t=bt,
                                 sampler=parsed, interpret=False)
    col, row = ((T_BULK,), U32), ((S_BULK,), U32)
    xs = ((T_BULK // bt, 4, S_BULK), U32)
    _assert_kernel(_compile_text(fn, one_chip, col, col, row, row, xs))


@pytest.mark.parametrize("mode", ["ctr", "faithful"])
def test_generate_windows_compiles(one_chip, monkeypatch, mode):
    """A fused producer's window stack, W windows as one wide window,
    with the counter traced as the producer passes it."""
    W = 4
    plan = engine.make_plan(seed=1, num_streams=S_BULK, num_steps=T_BULK,
                            mode=mode)
    monkeypatch.setattr(engine, "use_interpret", lambda: False)

    def fn(hi, lo):
        p = dataclasses.replace(plan, ctr=(hi, lo), offset=None)
        return engine.generate_windows(p, W, backend="pallas")
    scalar = ((), U32)
    _assert_kernel(_compile_text(fn, one_chip, scalar, scalar))


def test_fused_argmax_compiles(one_chip):
    V, B = 151_552, 128            # glm4-9b vocabulary, decode batch 128

    def fn(logits_t, h0, h1, r0, r1, c0, c1, thresh):
        return gumbel_argmax.fused_argmax(
            logits_t, (h0, h1), (r0, r1), (c0, c1), thresh,
            inv_temp=np.float32(1.0), interpret=False)
    vcol, brow = ((V,), U32), ((B,), U32)
    _assert_kernel(_compile_text(
        fn, one_chip, ((V, B), jnp.float32), brow, brow,
        vcol, vcol, vcol, vcol, ((B,), jnp.float32)))


@pytest.mark.parametrize("app", ["pi", "option"])
def test_mc_kernel_compiles(one_chip, app):
    T, S = 4_096, 65_536
    if app == "pi":
        kernel = mc.pi_partials
    else:
        kernel = functools.partial(mc.option_partials, s0=100.0,
                                   strike=100.0, r=0.05, sigma=0.2, t=1.0)

    def fn(r0, r1, c0, c1, hx0, hx1, hy0, hy1):
        return kernel((r0, r1), (c0, c1), (hx0, hx1), (hy0, hy1),
                      interpret=False)
    col, row = ((T,), U32), ((S,), U32)
    _assert_kernel(_compile_text(fn, one_chip, col, col, col, col,
                                 row, row, row, row))


def test_fused_dropout_compiles(one_chip):
    def fn(x, h0, h1, x00, x01, c0, c1):
        return fused_dropout.fused_dropout_2d(x, (h0, h1), (x00, x01),
                                              (c0, c1), 0.1, interpret=False)
    scalar = ((), U32)
    _assert_kernel(_compile_text(
        fn, one_chip, ((8_192, 4_096), jnp.bfloat16),
        scalar, scalar, scalar, scalar, scalar, scalar))
