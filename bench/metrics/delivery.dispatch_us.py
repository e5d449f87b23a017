"""Median ``blocks.dispatch`` span in microseconds: the host's time to
find a window's program, upload its counter and call it."""
from bench import program_spans, trace


def read(ctx):
    rec = program_spans.recorded(ctx)
    if rec is None:
        return None
    return trace.median([program_spans.us(s) for s in rec
                         if s.name == "blocks.dispatch"])
