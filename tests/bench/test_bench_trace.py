"""The trace reduction, on a trace recorded here on the CPU.

The CPU backend writes no device plane: its operations run on host
threads.  The test lifts those into a ``trace.Device`` and runs the same
reduction the TPU planes go through.
"""
from __future__ import annotations

import time

import pytest

from _bench_util import ROOT  # noqa: F401

from bench import trace


def _cpu_ops(profile):
    """The jitted function's operations, on whichever host thread ran
    them (the CPU client may run a small program on the caller's)."""
    ops = []
    for plane in profile.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                ops.extend(e for e in trace._events(line)
                           if e[0].startswith(("wrapped_sine", "dot")))
    return ops


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()
    tdir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(tdir)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.call"):
                    f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.pause"):
                    time.sleep(0.1)
    finally:
        jax.profiler.stop_trace()
    profile = trace.load(tdir)
    ops = _cpu_ops(profile)
    dev = trace.Device(name="cpu0", ops=ops, modules=[])
    return trace.reduce([dev], trace.host_spans(profile)), ops


def test_busy_and_idle_add_up_to_the_window(recorded):
    red, ops = recorded
    assert ops, "the CPU trace holds the jitted function's operations"
    assert red.window_s >= 0.3            # three 100 ms pauses
    assert 0 < red.busy_s < red.window_s
    idle = sum(e - s for d in red.devices for s, e, _ in d.gaps) / 1e9
    assert idle + red.busy_s == pytest.approx(red.window_s, rel=1e-6)
    assert red.idle_pct() == pytest.approx(
        100 * (1 - red.busy_s / red.window_s))


def test_op_time_by_name_and_gaps_by_host_span(recorded):
    red, _ = recorded
    dev = red.devices[0]
    assert any("dot" in n for n in dev.op_ns)
    assert sum(dev.op_ns.values()) >= dev.busy_ns   # overlaps count once
    paused = [e - s for s, e, span in dev.gaps if span == "bench.pause"]
    assert len(paused) == 3 and min(paused) > 95e6
    b = red.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][1] >= b["device_ops"][-1][1] > 0
    assert ["cpu0:bench.pause", min(paused) / 1e9] in b["idle_gaps"]


def test_launch_gaps_between_program_executions():
    mods = [("m", 0.0, 10.0), ("m", 15.0, 20.0), ("m", 20.0, 30.0),
            ("m", 70.0, 80.0)]
    dev = trace.Device(name="d", ops=[(n, s, e) for n, s, e in mods],
                       modules=mods)
    red = trace.reduce([dev], [("bench.window", 0.0, 100.0)])
    assert red.launch_gaps_ns() == [5.0, 0.0, 40.0]
    assert trace.median(red.launch_gaps_ns()) == 5.0
    assert red.devices[0].busy_ns == 35.0
    assert red.idle_pct() == pytest.approx(65.0)


def test_events_are_clipped_to_the_window_and_averaged_over_devices():
    a = trace.Device(name="a", ops=[("k", -5.0, 5.0), ("k", 90.0, 120.0)],
                     modules=[])
    b = trace.Device(name="b", ops=[("k", 0.0, 100.0)], modules=[])
    red = trace.reduce([a, b], [("bench.window", 0.0, 100.0),
                                ("bench.next", 10.0, 80.0)])
    assert red.op_ns(lambda n: n == "k") == [15.0, 100.0]
    assert red.busy_s == pytest.approx(57.5e-9)
    assert [g[2] for g in red.devices[0].gaps] == ["bench.next"]


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce([], [("bench.next", 0.0, 1.0)])
