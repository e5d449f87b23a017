"""Median over windows of the ledger's time for one window, in
microseconds: its ``blocks.lease`` plus its ``blocks.commit`` span,
joined by window id (the lease's ``lo``); windows with only one of the
two in the traced window are left out."""
from bench import program_spans, trace

LEDGER = ("blocks.lease", "blocks.commit")


def read(ctx):
    rec = program_spans.recorded(ctx)
    if rec is None:
        return None
    per = {}
    for s in rec:
        if s.name in LEDGER and s.window is not None:
            per.setdefault(s.window, {})[s.name] = program_spans.us(s)
    return trace.median([sum(d.values()) for d in per.values()
                         if len(d) == len(LEDGER)])
