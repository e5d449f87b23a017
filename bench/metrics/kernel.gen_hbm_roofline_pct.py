"""Generation kernel's share of the HBM roofline, in percent.

The least time the chips could take to write the traced window's samples
(windows x bytes per window from ``bench/work.py``, at the output width the
configuration states) at the published HBM rate of all the chips used,
over the device time of the generation, taken as the longest device's
operation time in the window.  Every device operation of a MISRN cell is
the producer's generation: the consumer launches nothing.
"""
from bench import work


def read(ctx):
    op_ns = ctx.trace.op_ns()
    windows = ctx.work.get("windows", 0)
    if ctx.peak is None or not op_ns or max(op_ns) <= 0 or not windows:
        return None
    least_s = (windows * work.window_bytes(ctx.cell)
               / (len(op_ns) * ctx.peak["hbm_bytes_per_s"]))
    return 100.0 * least_s / (max(op_ns) / 1e9)
