"""Share of the windows delivered in the traced window whose
``blocks.get`` found the producer's queue empty, in percent: how often
the consumer waited on the producer."""
from bench import program_spans


def read(ctx):
    rec = program_spans.recorded(ctx)
    if rec is None:
        return None
    gets = [s for s in rec if s.name == "blocks.get" and s.window is not None]
    if not gets:
        return None
    return 100.0 * sum(s.note == "empty" for s in gets) / len(gets)
