"""BENCHMARK.json against the benchmark's contract, and the harness's
lookup of cells, configurations, drivers and metrics by name."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from _bench_util import ROOT

from bench import harness, peaks, work

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
WORKLOADS = [w["name"] for w in SPEC["workloads"]]


def _one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_and_units_use_the_allowed_characters():
    names = ([c["name"] for c in SPEC["configs"]] + WORKLOADS
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
             + [w["traffic"] for w in SPEC["workloads"]]
             + [k for c in SPEC["configs"] for k in c["reduced"]])
    for n in names:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    metric_names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    assert len(set(WORKLOADS)) == len(WORKLOADS)


def test_entries_have_exactly_the_contract_keys():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _one_line(c["source"]) and _one_line(c["why"])
        assert c["file"].startswith("bench/configs/")
        assert (ROOT / c["file"]).is_file()
    pairs = set()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and _one_line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(SPEC["workloads"]) // 2)
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and _one_line(m["layer"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_workload_resolves_its_files_by_name(workload):
    cell = harness.resolve(workload)
    assert cell.config_name in {c["name"] for c in SPEC["configs"]}
    assert hasattr(harness.driver_of(cell), "measure")
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert hasattr(harness.reader_of(cell, m["name"]), "read")
        assert m["moves"] in e2e


def test_a_cell_and_a_metric_are_added_by_files_alone(tmp_path):
    """A later change adds files and entries; the harness finds them."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "dummy.cell", "config": "misrn_ctr_64k",
                              "traffic": "dummy_mix", "chips": 1,
                              "why": "a test cell"})
    spec["per_layer"].append({"name": "dummy.metric", "unit": "%",
                              "better": "lower", "source": "device_trace",
                              "layer": "device", "moves": "samples_per_s",
                              "workloads": ["dummy.cell"]})
    for m in spec["end_to_end"]:
        if "workloads" in m and m["name"] != "paths_per_s":
            m["workloads"].append("dummy.cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp_path / "bench/traffic/dummy_mix.json").write_text(json.dumps(
        {"driver": "misrn", "window_steps": 8, "warmup_windows": 1,
         "check_windows": 1}))
    (tmp_path / "bench/metrics/dummy.metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    cell = harness.resolve("dummy.cell", benchmark=tmp_path / "BENCHMARK.json",
                           bench_dir=tmp_path / "bench")
    assert cell.traffic["window_steps"] == 8
    assert [m["name"] for m in cell.per_layer] == ["dummy.metric"]
    assert harness.reader_of(cell, "dummy.metric").read(None) == 42.0
    assert harness.driver_of(cell).__file__.startswith(str(tmp_path))


@pytest.mark.parametrize("argv", [
    ["bench/run.py", "--trace", "0"],
    ["bench/control.py", "--fault", "wrong_counter", "--seeds", "1,2"],
])
def test_entry_points_exit_nonzero_with_no_result_off_the_tpu(argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    seed = [] if "--seeds" in argv else ["--seed", "1"]
    p = subprocess.run([sys.executable, *argv, "--workload", WORKLOADS[0],
                        *seed, "--seconds", "1"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


def test_work_counts_bytes_and_paths_from_the_cell_shapes():
    bulk = harness.resolve("misrn.ctr.bulk")
    assert work.samples_per_window(bulk) == 4096 * 65536
    assert work.window_bytes(bulk) == 4096 * 65536 * 4 == 2 ** 30
    small = harness.resolve("misrn.ctr.small_windows")
    assert work.window_bytes(small) == 64 * 65536 * 4 == 16 * 2 ** 20
    sharded = harness.Cell(
        name="sharded", chips=4, config_name="misrn_faithful_262k_4chip",
        traffic_name="closed_4096", config=json.loads(
            (ROOT / "bench/configs/misrn_faithful_262k_4chip.json")
            .read_text()),
        traffic=bulk.traffic, end_to_end=[], per_layer=[])
    assert work.window_bytes(sharded) == 4 * 2 ** 30
    mc = harness.resolve("mc.option.call")
    assert work.paths_per_call(mc) == 2 ** 28


def test_peaks_know_the_v5e_and_refuse_an_unknown_kind():
    v5e = peaks.peak("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v99")


def test_a_metric_named_for_one_cell_reads_its_base_quantity():
    """``<base>.<suffix>`` gives one cell its own bound on ``<base>``."""
    assert harness.base_name("window_p95_ms.small") == "window_p95_ms"
    assert harness.base_name("setup_s") == "setup_s"
    cell = harness.resolve("misrn.ctr.small_windows")
    assert "window_p95_ms.small" in {m["name"] for m in cell.end_to_end}
