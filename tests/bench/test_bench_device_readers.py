"""The device-trace readers of the sharded faithful cell, on hand-built
reduced traces whose values are worked out by hand, and the cell's entry
in ``BENCHMARK.json`` as the harness resolves it."""
from __future__ import annotations

import pytest

from _bench_util import ROOT  # noqa: F401

from bench import harness, trace, work

CELL = "misrn.faithful.sharded4"
PEAK = {"hbm_bytes_per_s": 819e9}
MS = 1e6                                   # ns

# Raw op names as the TPU's ops line gives them: a Mosaic call named by
# its HLO text, one named as the trace shortens it, and XLA ops (a
# ``while`` and a fusion of its body, nested under it).
MOSAIC = ('shard_map.165 = u32[4096,65536]{1,0} custom-call(u32[4096,1] '
          'p0), custom_call_target="tpu_custom_call"')
MOSAIC_SHORT = "_compute.1_custom-call_tpu_custom_call"
WHILE = "while.73 = (s32[], u32[65536,4]) while((s32[], u32[65536,4]) t)"
BODY = "fusion.9 = u32[65536,4] fusion(u32[65536,128,4] a), kind=kLoop"


def _dev(name, busy_ms, kernel_ms, while_ms, modules=()):
    """A chip whose busy time is its kernel and its ``while`` (the body's
    fusion nests inside the ``while``, so it adds to op time only)."""
    assert busy_ms == kernel_ms + while_ms
    return trace.DeviceReduced(
        name=name, busy_ns=busy_ms * MS,
        op_ns={MOSAIC: kernel_ms * MS / 2, MOSAIC_SHORT: kernel_ms * MS / 2,
               WHILE: while_ms * MS, BODY: 0.9 * while_ms * MS},
        gaps=[], modules=list(modules))


# Four chips, a 100 ms traced window.  Chip 2 is the slowest (most busy);
# chip 3 has the most kernel time.
DEVICES = [_dev("tpu0", 80, 60, 20), _dev("tpu1", 82, 61, 21),
           _dev("tpu2", 90, 63, 27), _dev("tpu3", 85, 65, 20)]


def _mods(ends_ms, length_ms=10.0):
    return [("jit_compute", (e - length_ms) * MS, e * MS) for e in ends_ms]


def _ctx(devices, windows=2, peak=PEAK):
    red = trace.Reduced(window=(0.0, 100 * MS), devices=list(devices))
    return trace.Context(cell=harness.resolve(CELL), trace=red,
                         work={"windows": windows}, peak=peak)


def _read(name, ctx):
    return harness.reader_of(ctx.cell, name).read(ctx)


def test_prep_share_is_the_slowest_chips_busy_time_outside_the_kernel():
    # tpu2: busy 90 ms, kernel 63 ms (both name forms) -> 27 / 90
    assert _read("engine.prep_pct", _ctx(DEVICES)) == pytest.approx(30.0)
    # one chip, 10 ms of prep in 40: the body's fusion nests inside its
    # while, and a sum over non-kernel ops would read (10 + 9) / 40
    only = [_dev("tpu0", 40, 30, 10)]
    assert _read("engine.prep_pct", _ctx(only)) == pytest.approx(25.0)


def test_faithful_roofline_is_the_least_write_time_over_the_slowest_kernel():
    cell = harness.resolve(CELL)
    least_s = 2 * work.window_bytes(cell) / (4 * PEAK["hbm_bytes_per_s"])
    # the slowest kernel is tpu3's 65 ms; the while is not kernel time
    want = 100.0 * least_s / 65e-3
    got = _read("kernel.faithful_hbm_roofline_pct", _ctx(DEVICES))
    assert got == pytest.approx(want)
    assert work.window_bytes(cell) == 4 * 2 ** 30
    assert got == pytest.approx(100 * 2 * 2 ** 30 / 819e9 / 65e-3)


def test_shard_skew_aligns_executions_and_drops_those_cut_by_the_edges():
    # Windows end every 10 ms.  tpu1 lost the first window at the traced
    # window's start edge, tpu3 the last one at its end edge.
    base = [12.0, 22.0, 32.0, 42.0, 52.0]
    late = {"tpu0": 0.0, "tpu1": 0.3, "tpu2": 0.1, "tpu3": 0.2}
    ends = {"tpu0": base, "tpu1": base[1:], "tpu2": base, "tpu3": base[:-1]}
    devs = [trace.DeviceReduced(
        name=n, busy_ns=50 * MS, op_ns={}, gaps=[],
        modules=_mods([e + late[n] + 0.01 * k
                       for k, e in enumerate(ends[n])]))
        for n in ("tpu0", "tpu1", "tpu2", "tpu3")]
    # windows 2-4 are on every chip; their spreads (ms), by hand:
    # w2: tpu0 22.01, tpu1 22.30, tpu2 22.11, tpu3 22.21 -> 0.29
    # w3: 32.02, 32.31, 32.12, 32.22 -> 0.29
    # w4: 42.03, 42.32, 42.13, 42.23 -> 0.29
    got = _read("device.shard_skew_us", _ctx(devs))
    assert got == pytest.approx(290.0)


def test_shard_skew_takes_the_median_over_windows():
    spreads = [0.05, 0.4, 0.1]               # ms, one slow window
    devs = []
    for i in range(4):
        ends = [10.0 * (k + 1) + (s if i == 3 else 0.0)
                for k, s in enumerate(spreads)]
        devs.append(trace.DeviceReduced(name=f"tpu{i}", busy_ns=30 * MS,
                                        op_ns={}, gaps=[],
                                        modules=_mods(ends)))
    assert _read("device.shard_skew_us", _ctx(devs)) == pytest.approx(100.0)


@pytest.mark.parametrize("name", ["engine.prep_pct",
                                  "kernel.faithful_hbm_roofline_pct",
                                  "device.shard_skew_us"])
def test_each_reader_is_silent_with_no_device(name):
    assert _read(name, _ctx([])) is None


def test_readers_are_silent_with_nothing_to_read():
    idle = [trace.DeviceReduced(name=f"tpu{i}", busy_ns=0.0, op_ns={},
                                gaps=[], modules=[]) for i in range(4)]
    assert _read("engine.prep_pct", _ctx(idle)) is None
    assert _read("device.shard_skew_us", _ctx(idle)) is None
    # no window delivered, no published peak (off the TPU), no kernel
    for ctx in (_ctx(DEVICES, windows=0), _ctx(DEVICES, peak=None),
                _ctx(idle)):
        assert _read("kernel.faithful_hbm_roofline_pct", ctx) is None
    # one chip: there is no spread between chips
    one = [trace.DeviceReduced(name="tpu0", busy_ns=30 * MS, op_ns={},
                               gaps=[], modules=_mods([10.0, 20.0]))]
    assert _read("device.shard_skew_us", _ctx(one)) is None


def test_the_sharded_cell_resolves_with_its_metrics():
    cell = harness.resolve(CELL)
    assert cell.chips == 4 and cell.config_name == "misrn_faithful_262k_4chip"
    assert cell.traffic_name == "closed_4096"
    assert cell.config["mode"] == "faithful"
    assert cell.config["mesh"]["shape"] == [4]
    assert {m["name"] for m in cell.end_to_end} == {"samples_per_s",
                                                    "setup_s"}
    layer = {m["name"]: m for m in cell.per_layer}
    for name in ("engine.prep_pct", "kernel.faithful_hbm_roofline_pct",
                 "device.shard_skew_us", "kernel.gen_hbm_roofline_pct",
                 "device.idle_pct.misrn", "delivery.launch_gap_us",
                 "delivery.producer_busy_pct", "delivery.starved_pct",
                 "delivery.dispatch_us", "delivery.ledger_us"):
        assert layer[name]["moves"] == "samples_per_s", name
    assert layer["engine.prep_pct"]["layer"] == "engine"
    assert layer["device.shard_skew_us"]["layer"] == "device"
    assert layer["kernel.faithful_hbm_roofline_pct"]["layer"] == "kernels"


@pytest.mark.parametrize("workload", ["misrn.ctr.bulk",
                                      "misrn.ctr.small_windows"])
def test_the_one_chip_misrn_cells_read_prep_share_only(workload):
    names = {m["name"] for m in harness.resolve(workload).per_layer}
    assert "engine.prep_pct" in names
    assert not names & {"kernel.faithful_hbm_roofline_pct",
                        "device.shard_skew_us"}
