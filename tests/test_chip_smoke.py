"""``chip_smoke.py``'s phases at tiny sizes on the CPU.

The phases run their kernels in interpret mode here (``select_backend``
is pointed at ``pallas`` so the kernel paths are the ones exercised),
which covers their control flow and comparison code; only a chip run
shows the Mosaic kernels.  Nothing here starts a process that needs a
chip: the four-device phase runs on four forced CPU devices.
"""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.core import engine

ROOT = Path(__file__).resolve().parents[1]


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod     # dataclasses resolve it by name
    spec.loader.exec_module(mod)
    return mod


cs = _load()


@pytest.fixture
def pallas(monkeypatch):
    monkeypatch.setattr(engine, "select_backend", lambda plan: "pallas")


def _assert_passed_off_chip(results):
    for r in results if isinstance(results, list) else [results]:
        assert r.ok, (r.name, r.checks)
        # interpret mode: the program holds no Mosaic kernel
        assert r.kernel in (None, False), r.name


def test_bulk_phase(pallas):
    results = cs.phase_bulk(streams=130, steps=40, short_steps=24,
                            golden_cols=8)
    assert [r.name for r in results] == [
        "bulk/ctr/bits/float32", "bulk/ctr/uniform/bfloat16",
        "bulk/ctr/normal/float32", "bulk/faithful/bits/float32"]
    assert all(r.backend == "pallas" for r in results)
    assert "golden" in results[0].checks
    _assert_passed_off_chip(results)


def test_delivery_phase(pallas):
    r = cs.phase_delivery(streams=130, window=16, windows=8, fuse=4)
    assert set(r.checks) == {"donation", "take", "producer"}
    _assert_passed_off_chip(r)


def test_service_phase(tmp_path):
    r = cs.phase_service(tenants=16, burst_size=24, journal_dir=tmp_path)
    assert (tmp_path / "service.jsonl").exists()
    _assert_passed_off_chip(r)


def test_tokens_phase():
    r = cs.phase_tokens(batch=8, vocab=256, max_steps=4)
    assert r.kernel is False
    _assert_passed_off_chip(r)


def test_apps_phase():
    results = cs.phase_apps(lanes=128, draws=256)
    assert [r.name for r in results] == ["apps/estimate_pi",
                                         "apps/price_option"]
    assert all("ref_gap" in r.detail for r in results)
    _assert_passed_off_chip(results)


def test_dropout_phase():
    r = cs.phase_dropout(rows=40, cols=256)
    _assert_passed_off_chip(r)


SHARDED_PROG = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import importlib.util
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
cs = importlib.util.module_from_spec(spec)
sys.modules["chip_smoke"] = cs
spec.loader.exec_module(cs)
from repro.core import engine
engine.select_backend = lambda plan: "pallas"
for r in cs.phase_sharded(streams=520, steps=24):
    print(r.name, r.ok, r.kernel)
"""


def test_sharded_phase_on_four_cpu_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", SHARDED_PROG,
                          str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    assert [ln.split()[0] for ln in lines] == [
        "sharded/ctr/1d", "sharded/ctr/2x2",
        "sharded/faithful/1d", "sharded/faithful/2x2"]
    assert all(ln.endswith("True False") for ln in lines), lines


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_main_refuses_off_tpu(argv, capsys):
    assert cs.main(argv) != 0
    captured = capsys.readouterr()
    assert "not 'tpu'" in captured.err
    assert not any(line.startswith("{")
                   for line in captured.out.splitlines())


def test_on_chip_verdict_needs_kernel_and_pallas():
    ok = dict(name="x", shapes="", first_s=0.0, steady_s=0.0,
              checks={"c": True})
    assert cs.passed(cs.Result(**ok, kernel=True, backend="pallas"))
    assert cs.passed(cs.Result(**ok))
    assert not cs.passed(cs.Result(**ok, kernel=False))
    assert not cs.passed(cs.Result(**ok, kernel=True, backend="xla"))
    bad = dict(ok, checks={"c": False})
    assert not cs.passed(cs.Result(**bad, kernel=True))
    assert not cs.Result(**dict(ok, checks={})).ok
    line = cs.Result(**bad, kernel=True, detail="gap=1").line()
    assert line.startswith("FAIL x ") and "gap=1" in line
    assert "failed=['c']" in line
