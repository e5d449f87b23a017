"""Median gap, in microseconds, between the end of one program execution
on the device and the start of the next: the delivery layer's per-window
cost as the device sees it (each window is one execution of the
producer's window program)."""
from bench import trace


def read(ctx):
    gap = trace.median(ctx.trace.launch_gaps_ns())
    return None if gap is None else gap / 1e3
