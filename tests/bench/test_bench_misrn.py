"""The MISRN cells' comparison, at a tiny size on the CPU.

The plain reference must agree with the program bit for bit, a sound run
must come out correct, and each fault the cells can have, planted under
the timed path, must turn ``correct`` false.  ``wrong_counter`` is the
control: it breaks counter addressing, the guarantee the configurations
state.
"""
from __future__ import annotations

import numpy as np
import pytest

from _bench_util import SEED, run_tiny

from bench.reference import ctr, faithful


@pytest.mark.parametrize("mode", ["ctr", "faithful"])
@pytest.mark.parametrize("lo", [0, 4160, 2 ** 40 + 3])
def test_reference_matches_the_program_and_sees_an_off_by_one(mode, lo):
    from repro.core import engine
    purpose = ctr.channel_purpose("bench/misrn")
    S, T = 200, 48
    plan = engine.make_plan(seed=SEED, num_streams=S, num_steps=T, offset=lo,
                            purpose=purpose, mode=mode)
    out = engine.generate(plan, backend="xla")
    cols = np.arange(S)
    stream = ctr.Stream(SEED, purpose, cols)

    def count(at):
        if mode == "ctr":
            return ctr.mismatches(out, at, stream, chunk=16)
        return faithful.mismatches(out, at, stream, cols, chunk=16)
    assert count(lo) == 0
    assert count(lo + 1) > S * T // 2


def test_faithful_reference_follows_global_stream_columns():
    from repro.core import engine
    purpose = ctr.channel_purpose("bench/misrn")
    plan = engine.make_plan(seed=SEED, num_streams=96, num_steps=16,
                            offset=32, purpose=purpose, mode="faithful")
    out = engine.generate(plan, backend="xla")
    cols = np.arange(40, 96)
    stream = ctr.Stream(SEED, purpose, cols)
    assert faithful.mismatches(out[:, 40:], 32, stream, cols, chunk=8) == 0
    assert faithful.mismatches(out[:, 40:], 32, stream, cols - 40,
                               chunk=8) > 0


@pytest.mark.parametrize("workload", ["misrn.ctr.bulk",
                                      "misrn.ctr.small_windows"])
def test_a_sound_run_is_correct(workload):
    r = run_tiny(workload)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    from bench import harness
    want = {m["name"] for m in harness.resolve(workload).end_to_end}
    assert set(r["metrics"]) == want
    assert {harness.base_name(n) for n in want} == {
        "samples_per_s", "window_p95_ms", "setup_s"}
    assert r["checks"]["mismatched_samples"]["value"] == 0
    assert list(r)[-2:] == ["checks", "_check_lines"]


@pytest.mark.parametrize("fault", ["wrong_counter", "stale_state",
                                   "half_batch", "altered"])
def test_each_fault_turns_correct_false(fault):
    r = run_tiny("misrn.ctr.bulk", faults=[fault])
    assert not r["correct"]
    assert r["checks"]["mismatched_samples"]["value"] > 0
    assert r["failed"] >= 1


def test_a_traced_run_reports_per_layer_metrics_only():
    r = run_tiny("misrn.ctr.bulk", trace=True)
    assert r["correct"]
    # the CPU has no device plane, so no reader finds anything to read
    assert r["metrics"] == {}
    assert r["device"]["window_s"] > 0 and "breakdown" in r
