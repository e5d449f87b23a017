"""The sharded faithful configuration's comparison on four CPU devices.

The tests see one device (tests/conftest.py), so a subprocess with four
host-platform devices runs the cell ``misrn.faithful.sharded4``
(``bench/configs/misrn_faithful_262k_4chip.json``), from a copy of
``BENCHMARK.json`` that lists it, at a tiny size: once as it is and once
with each fault planted.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from _bench_util import ROOT, SEED

FAULTS = ["wrong_counter", "stale_state", "half_batch", "altered",
          "shard_identity"]

SCRIPT = """
import json, sys, time
sys.path[:0] = [{root!r}, {src!r}]
from pathlib import Path
from bench import faults, harness
cell = harness.resolve("misrn.faithful.sharded4", benchmark=Path({spec!r}),
                       overrides={{
    "config": {{"num_streams": 512}},
    "traffic": {{"window_steps": 32, "warmup_windows": 1,
                 "check_windows": 2}}}})
for name in [None] + {faults!r}:
    r = harness.run_cell(cell, seed={seed}, seconds=0.3, trace=False,
                         t_start=time.perf_counter(), require_chip=False,
                         faults=[faults.FAULTS[name]] if name else [])
    r.pop("_check_lines")
    print(json.dumps({{"fault": name, "correct": r["correct"],
                       "count": r["device"]["count"],
                       "checks": r["checks"]}}), flush=True)
"""


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    if "misrn.faithful.sharded4" not in {w["name"] for w in spec["workloads"]}:
        spec["workloads"].append({"name": "misrn.faithful.sharded4",
                                  "config": "misrn_faithful_262k_4chip",
                                  "traffic": "closed_4096", "chips": 4,
                                  "why": "sharded faithful windows"})
    path = tmp_path_factory.mktemp("spec") / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SCRIPT.format(root=str(ROOT), src=str(ROOT / "src"),
                         spec=str(path), faults=FAULTS, seed=SEED)
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    rows = [json.loads(line) for line in p.stdout.splitlines()
            if line.startswith("{")]
    return {row["fault"]: row for row in rows}


def test_a_sound_sharded_run_is_correct_on_every_shard(results):
    row = results[None]
    assert row["count"] == 4
    assert row["correct"], row["checks"]


@pytest.mark.parametrize("fault", FAULTS)
def test_each_fault_turns_the_sharded_cell_false(results, fault):
    row = results[fault]
    assert not row["correct"]
    assert row["checks"]["mismatched_samples"]["value"] > 0
