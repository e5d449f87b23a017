"""The program's own host spans of a traced window, for per-layer readers.

``repro.runtime.spans`` records spans only while the profiler runs, so
what it holds after a ``--trace 1`` run are the traced window's spans,
and those the producer thread records between the window's end and the
profiler's stop: a window or two, before its queue is full again.  A
program that has no such module, or a run on a host with no device
plane, gives the readers nothing to read.
"""
from __future__ import annotations

from typing import List, Optional


def recorded(ctx) -> Optional[List]:
    """The spans recorded in the traced window, or None if there are none
    or the run saw no device (off the TPU, as the device readers)."""
    if not ctx.trace.devices:
        return None
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    return spans.recorded() or None


def us(s) -> float:
    """A span's duration in microseconds."""
    return (s.end_ns - s.start_ns) / 1e3
