"""The readers of the program's delivery spans, on hand-built spans whose
values are worked out by hand, and their silence where there is nothing
to read: no device plane, or a program without ``repro.runtime.spans``."""
from __future__ import annotations

import sys

import pytest

from _bench_util import ROOT  # noqa: F401

from bench import harness, trace
from repro.runtime import spans

PROD = "blocks:bench/misrn"
MAIN = "MainThread"
US = 1_000


def _s(name, window, thread, start_us, end_us, note=None):
    return spans.Span(name, window, thread, start_us * US, end_us * US, note)


# A 10 ms traced window, three windows of 64 steps (lo 0, 64, 128); the
# fourth lease (lo 192) falls at the end, its commit after the profiler.
SPANS = [
    _s("blocks.lease", 0, PROD, 0, 20),
    _s("blocks.dispatch", 0, PROD, 20, 320),
    _s("blocks.put", 0, PROD, 320, 330),
    _s("blocks.get", 0, MAIN, 100, 330, "empty"),
    _s("blocks.commit", 0, MAIN, 330, 340),
    _s("blocks.lease", 64, PROD, 400, 430),
    _s("blocks.dispatch", 64, PROD, 430, 930),
    _s("blocks.put", 64, PROD, 930, 935),
    _s("blocks.get", 64, MAIN, 500, 935, "empty"),
    _s("blocks.commit", 64, MAIN, 935, 975),
    _s("blocks.lease", 128, PROD, 1000, 1010),
    _s("blocks.dispatch", 128, PROD, 1010, 1410),
    _s("blocks.put", 128, PROD, 1410, 1420),
    _s("blocks.get", 128, MAIN, 1500, 1505),
    _s("blocks.commit", 128, MAIN, 1505, 1525),
    _s("blocks.lease", 192, PROD, 1600, 1650),
    _s("blocks.get", None, MAIN, 9000, 9100, "empty"),   # end of stream
    # the pricing loop's lease on the main thread is no producer work
    _s("blocks.lease", 5000, MAIN, 2000, 2500),
]

# by hand: producer lease 20 + 30 + 10 + 50 = 110 us, dispatch
# 300 + 500 + 400 = 1200 us: 1310 us of 10,000 us
EXPECTED = {
    "delivery.producer_busy_pct": 13.1,
    "delivery.starved_pct": 100.0 * 2 / 3,
    "delivery.dispatch_us": 400.0,
    # windows 0: 20 + 10, 64: 30 + 40, 128: 10 + 20 -> 30, 70, 30
    "delivery.ledger_us": 30.0,
}


def _ctx(devices):
    dev = trace.DeviceReduced(name="tpu0", busy_ns=1e6, op_ns={}, gaps=[],
                              modules=[])
    red = trace.Reduced(window=(0.0, 10e6),
                        devices=[dev] if devices else [])
    cell = harness.resolve("misrn.ctr.small_windows")
    return trace.Context(cell=cell, trace=red, work={"windows": 3},
                         peak=None)


def _read(name, ctx):
    return harness.reader_of(ctx.cell, name).read(ctx)


@pytest.fixture
def recorded(monkeypatch):
    monkeypatch.setattr(spans, "recorded", lambda: list(SPANS))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_reader_gives_the_value_worked_out_by_hand(recorded, name):
    assert _read(name, _ctx(True)) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_reader_is_silent_without_devices(recorded, name):
    assert _read(name, _ctx(False)) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_reader_is_silent_with_no_spans(monkeypatch, name):
    monkeypatch.setattr(spans, "recorded", lambda: [])
    assert _read(name, _ctx(True)) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_reader_is_silent_on_a_program_without_spans(recorded,
                                                         monkeypatch, name):
    # a program that predates repro.runtime.spans: the import fails
    import repro.runtime
    monkeypatch.delattr(repro.runtime, "spans")
    monkeypatch.setitem(sys.modules, "repro.runtime.spans", None)
    with pytest.raises(ImportError):
        from repro.runtime import spans as _  # noqa: F401
    assert _read(name, _ctx(True)) is None


def test_the_metrics_are_the_delivery_layers_and_read_misrn_cells():
    for cell_name in ("misrn.ctr.bulk", "misrn.ctr.small_windows"):
        cell = harness.resolve(cell_name)
        mine = {m["name"]: m for m in cell.per_layer
                if m["source"] == "program_span"}
        assert set(mine) == set(EXPECTED)
        assert {m["layer"] for m in mine.values()} == {"delivery"}
        assert {m["moves"] for m in mine.values()} == {"samples_per_s"}
    mc = harness.resolve("mc.option.call")
    assert not [m for m in mc.per_layer if m["source"] == "program_span"]
