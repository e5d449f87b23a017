"""Helpers shared by the benchmark's tests: tiny sizes for the CPU."""
from __future__ import annotations

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

SEED = 2 ** 31 + 4099      # above 32 signed bits, as the driver's seeds are

TINY = {
    "misrn.ctr.bulk": {"config": {"num_streams": 256},
                       "traffic": {"window_steps": 64, "warmup_windows": 2,
                                   "check_windows": 3}},
    "misrn.ctr.small_windows": {"config": {"num_streams": 256},
                                "traffic": {"warmup_windows": 2,
                                            "check_windows": 4}},
    "mc.option.call": {"config": {"num_lanes": 256},
                        "traffic": {"draws_per_call": 64, "warmup_calls": 1,
                                    "check_calls": 3}},
}


def run_tiny(workload, *, faults=(), seconds=0.3, trace=False, seed=SEED,
             overrides=None):
    """One run of a cell at a tiny size on the CPU, faults planted."""
    from bench import faults as faults_mod, harness
    cell = harness.resolve(workload, overrides=overrides or TINY[workload])
    return harness.run_cell(
        cell, seed=seed, seconds=seconds, trace=trace,
        t_start=time.perf_counter(), require_chip=False,
        faults=[faults_mod.FAULTS[f] for f in faults])
