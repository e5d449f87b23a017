"""Host spans of the delivery layer (``repro.runtime.spans``): off without
a profiler session, on the profiler's clock with one, bounded, and joined
across the producer and consumer threads by the window's lease ``lo``."""
import glob
import os
import threading
import time

import jax
import numpy as np
import pytest

from repro.runtime import BlockService, spans

NAMES = ("blocks.lease", "blocks.dispatch", "blocks.put", "blocks.get",
         "blocks.commit")
WINDOW = 4


def _consume(svc, n, pause=0.0):
    """Leases ``lo`` of ``n`` windows taken from a producer, ready."""
    lows = []
    with svc.producer("s", WINDOW, count=n) as prod:
        for lease, blk in prod:
            jax.block_until_ready(blk)
            lows.append(lease.lo)
            time.sleep(pause)
    return lows


def _service():
    svc = BlockService(seed=5)
    svc.open("s", num_streams=8)
    svc.take("s", WINDOW).block_until_ready()      # compile outside
    return svc


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A producer run of 4 windows under the profiler: its windows' lows,
    the spans recorded and the profile written."""
    svc = _service()
    spans.clear()
    tdir = str(tmp_path_factory.mktemp("spans"))
    jax.profiler.start_trace(tdir)
    try:
        lows = _consume(svc, 4)
    finally:
        jax.profiler.stop_trace()
    rec = spans.recorded()
    spans.clear()
    path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                     recursive=True)
    return lows, rec, jax.profiler.ProfileData.from_file(path[0])


def test_the_profiler_probe_exists():
    from jax._src.lib import _profiler
    assert callable(_profiler.TraceMe.is_enabled), (
        "jax no longer has TraceMe.is_enabled: repro.runtime.spans would "
        "never record")
    assert spans.PROBE


def test_without_a_profiler_nothing_is_recorded(monkeypatch):
    made = []
    real = jax.profiler.TraceAnnotation

    def counting(*a, **k):
        made.append(a)
        return real(*a, **k)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    svc = _service()
    spans.clear()
    assert len(_consume(svc, 3)) == 3
    assert spans.recorded() == [] and spans.dropped() == 0
    assert made == []


def test_each_span_is_recorded_under_the_profiler(traced):
    lows, rec, _ = traced
    assert lows == [WINDOW * i for i in range(1, 5)]
    assert {s.name for s in rec} == set(NAMES)
    assert all(s.end_ns >= s.start_ns for s in rec)


def test_a_windows_spans_share_its_lease_lo(traced):
    lows, rec, _ = traced
    producer = {s.thread for s in rec if s.name == "blocks.put"}
    consumer = threading.current_thread().name
    assert producer == {"blocks:s"}
    for lo in lows:
        mine = {(s.name, s.thread) for s in rec if s.window == lo}
        assert mine == {("blocks.lease", "blocks:s"),
                        ("blocks.dispatch", "blocks:s"),
                        ("blocks.put", "blocks:s"),
                        ("blocks.get", consumer),
                        ("blocks.commit", consumer)}, lo


def test_recorded_spans_match_their_xplane_events(traced):
    _, rec, profile = traced
    env = profile.find_plane_with_name("Task Environment")
    t0 = dict(env.stats)["profile_start_time"]
    events = {}
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in NAMES:
                        events.setdefault(e.name, []).append(
                            t0 + e.start_ns)
    for name in NAMES:
        mine = sorted(s.start_ns for s in rec if s.name == name)
        theirs = sorted(events.get(name, []))
        assert len(mine) == len(theirs) > 0, name
        gap = np.max(np.abs(np.subtract(mine, theirs, dtype=np.float64)))
        assert gap <= 200e3, (name, gap)


def test_the_consumer_note_says_whether_the_queue_was_empty(tmp_path):
    def slow(lo, hi):
        time.sleep(0.05)
        return np.full((hi - lo, 2), lo, np.uint32)

    svc = BlockService(seed=5)
    svc.open("s", window_fn=slow)
    spans.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        # the first get waits on a 50 ms window; after a 0.3 s pause the
        # second window is queued
        _consume(svc, 2, pause=0.3)
    finally:
        jax.profiler.stop_trace()
    # the third get takes the end of the stream, which has no window
    gets = [s for s in spans.recorded()
            if s.name == "blocks.get" and s.window is not None]
    spans.clear()
    assert [(s.window, s.note) for s in gets] == [(0, "empty"), (4, None)]


def test_the_ring_keeps_its_bound_and_counts_what_it_drops(monkeypatch,
                                                           tmp_path):
    monkeypatch.setattr(spans, "_ring", spans._Ring(4))
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(10):
            with spans.span("test.ring") as sp:
                sp.window = i
    finally:
        jax.profiler.stop_trace()
    assert [s.window for s in spans.recorded()] == [6, 7, 8, 9]
    assert spans.dropped() == 6
    spans.clear()
    assert spans.recorded() == [] and spans.dropped() == 0
