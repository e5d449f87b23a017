"""Median over windows of the spread, in microseconds, between the first
and the last chip to finish the window's program: the straggler cost of
one program run on several chips.

Each window is one execution of the window program on every chip (the
device's module line).  The k-th executions of the chips are aligned:
at the traced window's edges a chip may hold one execution more or less
than another, so each chip's list is shifted against the first chip's by
the offset (at most ``MAX_SHIFT``) that brings their end times closest,
and executions not present on every chip are dropped.
"""
import statistics

from bench import trace

MAX_SHIFT = 2


def _cost(ref, ends, shift):
    diffs = [abs(ends[i + shift] - ref[i]) for i in range(len(ref))
             if 0 <= i + shift < len(ends)]
    return statistics.median(diffs) if diffs else float("inf")


def read(ctx):
    devs = ctx.trace.devices
    if len(devs) < 2:
        return None
    ends = [sorted(e for _, _, e in d.modules) for d in devs]
    if not all(ends):
        return None
    ref = ends[0]
    shifts = [min(range(-MAX_SHIFT, MAX_SHIFT + 1),
                  key=lambda s: (_cost(ref, e, s), abs(s))) for e in ends]
    skews = []
    for k in range(len(ref)):
        idx = [k + s for s in shifts]
        if all(0 <= i < len(e) for i, e in zip(idx, ends)):
            at = [e[i] for i, e in zip(idx, ends)]
            skews.append(max(at) - min(at))
    skew = trace.median(skews)
    return None if skew is None else skew / 1e3
