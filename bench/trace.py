"""Reduction of a profiler trace to the benchmark's device numbers.

A ``--trace 1`` run records a JAX profiler trace (``.xplane.pb``) around
its measured window.  This module reads it with ``jax.profiler.ProfileData``
and reduces it, per device, to

* busy time: the union of the intervals in which an operation ran,
  clipped to the window (the host span ``bench.window``);
* operation time by name (each operation's duration inside the window);
* idle gaps: the window minus the busy intervals, each named by the
  innermost ``bench.*`` host span that was open at its midpoint;
* program executions (the device's module line), for launch gaps.

On a TPU the device planes are ``/device:TPU:<id>``, their operations on
the line ``XLA Ops`` and their program executions on ``XLA Modules``.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import statistics
from typing import Any, Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Device:
    name: str
    ops: List[Event]
    modules: List[Event]


@dataclasses.dataclass
class DeviceReduced:
    name: str
    busy_ns: float
    op_ns: Dict[str, float]
    gaps: List[Tuple[float, float, str]]   # (start, end, host span)
    modules: List[Event]


@dataclasses.dataclass
class Reduced:
    window: Tuple[float, float]
    devices: List[DeviceReduced]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(d.busy_ns for d in self.devices) / len(self.devices) / 1e9

    def idle_pct(self) -> Optional[float]:
        if not self.devices or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def op_ns(self, match=lambda name: True) -> List[float]:
        """Per device: summed time of the operations whose name matches."""
        return [sum(t for n, t in d.op_ns.items() if match(n))
                for d in self.devices]

    def launch_gaps_ns(self) -> List[float]:
        """Gaps between consecutive program executions on each device."""
        gaps: List[float] = []
        for d in self.devices:
            mods = sorted(d.modules, key=lambda e: e[1])
            for a, b in zip(mods, mods[1:]):
                gaps.append(max(0.0, b[1] - a[2]))
        return gaps

    def breakdown(self, top: int = 10) -> Dict[str, List[List[Any]]]:
        """The device ops that took most time (seconds, averaged over the
        devices) and the longest idle gaps, named by the host span."""
        total: Dict[str, float] = {}
        for d in self.devices:
            for n, t in d.op_ns.items():
                total[n] = total.get(n, 0.0) + t
        n_dev = max(1, len(self.devices))
        ops = sorted(total.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(((e - s, f"{d.name}:{span}") for d in self.devices
                       for s, e, span in d.gaps), key=lambda g: -g[0])[:top]
        return {"device_ops": [[short_name(n), t / n_dev / 1e9]
                               for n, t in ops],
                "idle_gaps": [[n, t / 1e9] for t, n in gaps]}


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class _Spans:
    """Innermost host span open at a time."""

    def __init__(self, spans: List[Event]):
        self.spans = sorted((s for s in spans if s[0] != WINDOW_SPAN),
                            key=lambda e: e[1])
        self.starts = [s[1] for s in self.spans]

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t)
        best = None
        # spans are short and nest little; look back over a few
        for name, s, e in self.spans[max(0, i - 64):i]:
            if s <= t <= e and (best is None or e - s < best[1]):
                best = (name, e - s)
        return best[0] if best else "outside bench spans"


def reduce(devices: List[Device], spans: List[Event]) -> Reduced:
    """Reduce device events against the host spans of one window."""
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} host span")
    _, w0, w1 = windows[0]
    host = _Spans([s for s in spans if s[0].startswith(SPAN_PREFIX)])
    out = []
    for dev in devices:
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in dev.ops
                   if e > w0 and s < w1]
        busy = _union((s, e) for _, s, e in clipped)
        op_ns: Dict[str, float] = {}
        for n, s, e in clipped:
            op_ns[n] = op_ns.get(n, 0.0) + (e - s)
        gaps = []
        cursor = w0
        for s, e in busy + [(w1, w1)]:
            if s > cursor:
                gaps.append((cursor, s, host.at((cursor + s) / 2)))
            cursor = max(cursor, e)
        mods = [m for m in dev.modules if m[2] > w0 and m[1] < w1]
        out.append(DeviceReduced(
            name=dev.name, busy_ns=sum(e - s for s, e in busy), op_ns=op_ns,
            gaps=gaps, modules=mods))
    return Reduced(window=(w0, w1), devices=out)


_OPCODE = re.compile(r" = .*? ([a-z][a-z\-]*)\(")


def short_name(name: str) -> str:
    """An HLO op's trace name cut to its instruction name and opcode,
    with the custom-call target for a Mosaic kernel."""
    if " = " not in name:
        return name
    out = name.split(" = ", 1)[0]
    m = _OPCODE.search(name)
    if m:
        out += " " + m.group(1)
    if 'custom_call_target="tpu_custom_call"' in name:
        out += " tpu_custom_call"
    return out


def _events(line) -> List[Event]:
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def load(tdir: str):
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {tdir}")
    return ProfileData.from_file(paths[-1])


def host_spans(profile) -> List[Event]:
    out: List[Event] = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend(e for e in _events(line)
                           if e[0].startswith(SPAN_PREFIX))
    return out


def tpu_devices(profile, ids: Optional[List[int]] = None) -> List[Device]:
    """The TPU device planes (only ``ids`` when given)."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        try:
            idx = int(plane.name.rsplit(":", 1)[1])
        except ValueError:
            continue
        if ids is not None and idx not in ids:
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        out.append(Device(
            name=f"tpu{idx}", ops=_events(lines[OPS_LINE]),
            modules=(_events(lines[MODULES_LINE])
                     if MODULES_LINE in lines else [])))
    return sorted(out, key=lambda d: d.name)


def reduce_dir(tdir: str, devices: Optional[List[int]] = None) -> Reduced:
    profile = load(tdir)
    return reduce(tpu_devices(profile, devices), host_spans(profile))


@dataclasses.dataclass
class Context:
    """What a per-layer reader may read."""
    cell: Any
    trace: Reduced
    work: Dict[str, Any]
    peak: Optional[Dict[str, float]]


def median(values: List[float]) -> Optional[float]:
    return statistics.median(values) if values else None
