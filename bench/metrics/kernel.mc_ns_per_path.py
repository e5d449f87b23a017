"""Device time of the fused option-pricing kernel per path priced, in ns.

The kernel's operations are the Mosaic custom calls (``tpu_custom_call``)
of the trace: the option kernel is the only Pallas kernel a pricing call
launches.  Their time is averaged over the devices used and divided by the
paths that the window's calls priced (``bench/work.py``).
"""
from bench import work

KERNEL = "tpu_custom_call"


def read(ctx):
    op_ns = ctx.trace.op_ns(lambda name: KERNEL in name)
    calls = ctx.work.get("calls", 0)
    if not op_ns or not calls or sum(op_ns) <= 0:
        return None
    return (sum(op_ns) / len(op_ns)) / (calls * work.paths_per_call(ctx.cell))
