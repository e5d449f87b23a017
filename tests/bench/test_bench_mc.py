"""The option-pricing cell's comparison, at a tiny size on the CPU.

The control puts the plain reference, computed in bfloat16 (the precision
below the float32 the configuration states), in the program's place; it
and each fault must fail ``lane_gap``, and a sound run must pass it.
"""
from __future__ import annotations

import json

import jax.numpy as jnp
import numpy as np
import pytest

from _bench_util import ROOT, SEED, run_tiny

from bench.reference import ctr, option

OPTION = json.loads((ROOT / "bench/configs/mc_option_bs.json").read_text())[
    "option"]
PARAMS = tuple(OPTION[k] for k in ("s0", "strike", "r", "sigma", "t"))


@pytest.mark.parametrize("lo", [0, 2 ** 36 + 64])
def test_reference_matches_the_program_kernel(lo):
    from repro.kernels import mc, ops
    lanes, draws = 256, 48
    px, py = ops._mc_plans(SEED, lanes, draws, 3, 4, lo)
    part = mc.option_partials_from_plans(
        px, py, **OPTION, interpret=True)
    got = np.asarray(jnp.sum(part, axis=0), np.float64)
    cols = np.arange(lanes)
    x, y = ctr.Stream(SEED, 3, cols), ctr.Stream(SEED, 4, cols)
    want = np.asarray(option.lane_sums(lo, draws, x, y, PARAMS, chunk=16))
    low = np.asarray(option.lane_sums(lo, draws, x, y, PARAMS,
                                      dtype=jnp.bfloat16, chunk=16))
    scale = np.mean(np.abs(want))
    assert np.max(np.abs(got - want)) / scale < 1e-5
    assert np.max(np.abs(low - want)) / scale > 1e-3


def test_a_sound_run_is_correct():
    r = run_tiny("mc.option.call")
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"paths_per_s", "setup_s"}
    assert r["checks"]["lane_gap"]["value"] <= r["checks"]["lane_gap"]["limit"]


@pytest.mark.parametrize("fault", ["reference_bf16", "wrong_counter",
                                   "stale_state", "half_batch", "altered"])
def test_the_control_and_each_fault_turn_correct_false(fault):
    r = run_tiny("mc.option.call", faults=[fault])
    assert not r["correct"]
    assert r["checks"]["lane_gap"]["value"] > r["checks"]["lane_gap"]["limit"]
