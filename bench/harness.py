"""The benchmark's harness: finds a cell's files by name and runs it.

``BENCHMARK.json`` names each cell (workload) with its configuration and
traffic mix.  Everything else is found by name under this directory:

* ``configs/<config>.json``   one deployment's sizes and guarantees;
* ``traffic/<traffic>.json``  one traffic mix: its driver and parameters;
* ``drivers/<driver>.py``     one closed loop and its comparison;
* ``metrics/<metric>.py``     one per-layer reader over the reduced trace.

A later change adds a cell, a configuration or a metric by adding such
files and entries; nothing here changes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
CACHE_DIR = ROOT / ".bench_cache"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass(frozen=True)
class Check:
    """One number compared with its limit (``kind``: max or min)."""
    name: str
    value: float
    limit: float
    kind: str = "max"

    @property
    def ok(self) -> bool:
        if self.kind == "max":
            return self.value <= self.limit
        return self.value >= self.limit

    def line(self) -> str:
        op = "<=" if self.kind == "max" else ">="
        return (f"check {self.name} {self.value!r} {op} {self.limit!r} "
                f"{'ok' if self.ok else 'FAIL'}")


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files resolved."""
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: Path = BENCH_DIR

    @property
    def driver_name(self) -> str:
        return self.traffic["driver"]


def load_module(path: Path, name: Optional[str] = None) -> ModuleType:
    """Import a file by path (names may hold dots, as metric names do)."""
    name = name or "bench_file_" + str(path.resolve()).replace(
        "/", "_").replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def _applies(metric: Dict[str, Any], cell: str, e2e_here: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    return moves is None or moves in e2e_here


def resolve(workload: str, *, benchmark: Optional[Path] = None,
            bench_dir: Path = BENCH_DIR,
            overrides: Optional[Dict[str, Dict[str, Any]]] = None) -> Cell:
    """The cell ``workload`` of ``benchmark`` with its files read.

    ``overrides`` ({"config": {...}, "traffic": {...}}) replaces keys of
    the two files; tests use it to run a cell at a tiny size.
    """
    spec = json.loads((benchmark or bench_dir.parent / "BENCHMARK.json")
                      .read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    config = json.loads((bench_dir / "configs" / f"{w['config']}.json")
                        .read_text())
    traffic = json.loads((bench_dir / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    for key, target in (("config", config), ("traffic", traffic)):
        target.update((overrides or {}).get(key, {}))
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"] if _applies(m, workload, names)]
    return Cell(name=workload, chips=int(w["chips"]),
                config_name=w["config"], traffic_name=w["traffic"],
                config=config, traffic=traffic, end_to_end=e2e,
                per_layer=layer, root=bench_dir)


def driver_of(cell: Cell) -> ModuleType:
    return load_module(cell.root / "drivers" / f"{cell.driver_name}.py")


def base_name(name: str) -> str:
    """``window_p95_ms.small`` -> ``window_p95_ms``: a metric given a
    cell's own name (and bound) is the same quantity as its base."""
    return name.rsplit(".", 1)[0]


def reader_of(cell: Cell, metric: str) -> ModuleType:
    return load_module(cell.root / "metrics" / f"{metric}.py")


def devices_for(cell: Cell, require_chip: bool = True):
    """The cell's devices; raises ``NoChip`` off the TPU or when short."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no backend: {e}") from e
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"JAX runs on {devs[0].platform!r}, not on a TPU")
    if len(devs) < cell.chips:
        raise NoChip(f"the cell needs {cell.chips} chips, JAX sees "
                     f"{len(devs)}")
    return devs[:cell.chips]


class CompileCounter:
    """Counts executables compiled (or fetched from the persistent cache)
    while ``armed``: inside the measured window there should be none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax
        self.armed = False
        self.count = 0
        self.before = 0          # compiled before the window: set-up
        self.before_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_: Any) -> None:
        if event != self.EVENT:
            return
        if self.armed:
            self.count += 1
        else:
            self.before += 1
            self.before_s += duration


def _nospan(name: str):
    return contextlib.nullcontext()


def _memory_peak(devs) -> Optional[int]:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, require_chip: bool = True,
             faults: Optional[List[Any]] = None,
             log: Callable[[str], None] = lambda s: None) -> Dict[str, Any]:
    """Set up, measure, check; returns the result line as a dict.

    ``faults`` (see ``bench/faults.py``) are planted under the timed
    path before set-up; the control and the fault tests use them.
    """
    import jax
    from bench import peaks, trace as trace_mod

    devs = devices_for(cell, require_chip)
    log(f"devices ready {time.perf_counter() - t_start:.3f}s")
    driver = driver_of(cell)
    faults = list(faults or [])
    with contextlib.ExitStack() as stack:
        for f in faults:
            stack.enter_context(f.planted(cell))
        counter = CompileCounter()
        span = jax.profiler.TraceAnnotation if trace else _nospan
        state = driver.setup(cell, seed, devs)
        for f in faults:
            f.after_setup(cell, state)
        setup_s = time.perf_counter() - t_start
        log(f"setup {setup_s:.3f}s ({counter.before} compiles, "
            f"{counter.before_s:.3f}s)")
        window = float(seconds)
        tdir = None
        if trace:
            # the profiler covers the first trace_seconds; the run then
            # goes on untraced for the rest of its length
            window = min(window, float(cell.traffic.get("trace_seconds",
                                                        window)))
            tdir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(tdir)
        gc_before = [g["collections"] for g in gc.get_stats()]
        counter.armed = True
        try:
            try:
                with span("bench.window"):
                    measured = driver.measure(state, window, span)
            finally:
                if trace:
                    jax.profiler.stop_trace()
            if trace and seconds > window:
                rest = driver.measure(state, seconds - window, _nospan)
                measured["attempted"] += rest["attempted"]
        finally:
            counter.armed = False
        gc_runs = [g["collections"] - b
                   for g, b in zip(gc.get_stats(), gc_before)]
        log(f"window: gc collections by generation {gc_runs}")
        if measured.get("waits"):
            log(f"waits {json.dumps(measured['waits'])}")
        memory = _memory_peak(devs)
        verdict = driver.check(state)
    checks = list(verdict["checks"])
    checks.append(Check("compiles_in_window", counter.count, 0))
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": memory}
    metrics: Dict[str, Dict[str, Any]] = {}
    out: Dict[str, Any] = {}
    if trace:
        try:
            reduced = trace_mod.reduce_dir(
                tdir, devices=[d.id for d in devs] if dev.platform == "tpu"
                else None)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        ctx = trace_mod.Context(cell=cell, trace=reduced,
                                work=measured["work"],
                                peak=(peaks.peak(dev.device_kind)
                                      if dev.platform == "tpu" else None))
        for m in cell.per_layer:
            value = reader_of(cell, m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["breakdown"] = reduced.breakdown()
    else:
        values = dict(measured["end_to_end"], setup_s=setup_s)
        for m in cell.end_to_end:
            name = m["name"]
            value = values[name if name in values else base_name(name)]
            metrics[name] = {"value": value, "unit": m["unit"]}
    return {"correct": all(c.ok for c in checks),
            "attempted": measured["attempted"],
            "failed": verdict["failed"],
            "metrics": metrics, "device": device, **out,
            "checks": {c.name: {"value": c.value, "limit": c.limit,
                                "kind": c.kind} for c in checks},
            "_check_lines": [c.line() for c in checks]}


def emit(result: Dict[str, Any]) -> None:
    """Checks as the last lines of stderr; the result as stdout's last."""
    lines = result.pop("_check_lines")
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def use_bench_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, and the
    TPU runtime's logs beside it (its default is a fixed path in /tmp).
    Call before JAX is imported."""
    path = CACHE_DIR / "jax"
    path.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(path)
    os.environ["TPU_LOG_DIR"] = str(CACHE_DIR / "tpu_logs")
    return str(path)
