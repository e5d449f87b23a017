"""Share of the traced window in which the producer thread leased or
dispatched, in percent: 100 x the summed ``blocks.lease`` and
``blocks.dispatch`` spans of the thread that puts windows on the queue
(``blocks.put``), over the window.  Near 100 the producer sets the pace."""
from bench import program_spans

BUSY = ("blocks.lease", "blocks.dispatch")


def read(ctx):
    rec = program_spans.recorded(ctx)
    if rec is None or ctx.trace.window_s <= 0:
        return None
    producers = {s.thread for s in rec if s.name == "blocks.put"}
    if not producers:
        return None
    busy_us = max(sum(program_spans.us(s) for s in rec
                      if s.thread == t and s.name in BUSY)
                  for t in producers)
    return 100.0 * busy_us / 1e6 / ctx.trace.window_s
