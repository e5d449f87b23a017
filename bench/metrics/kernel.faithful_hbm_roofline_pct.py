"""Faithful generation kernel's share of the HBM roofline, in percent.

The least time the chips could take to write the traced window's samples
(windows x bytes per window from ``bench/work.py``, at the output width the
configuration states) at the published HBM rate of all the chips used,
over the slowest chip's Mosaic-kernel time alone: the ops that
``trace.short_name`` marks ``tpu_custom_call``.  The start-state jumps
before the kernel are left out (``engine.prep_pct`` reads them).
"""
from bench import trace, work

KERNEL = "tpu_custom_call"


def read(ctx):
    kernel_ns = ctx.trace.op_ns(lambda n: KERNEL in trace.short_name(n))
    windows = ctx.work.get("windows", 0)
    if (ctx.peak is None or not kernel_ns or max(kernel_ns) <= 0
            or not windows):
        return None
    least_s = (windows * work.window_bytes(ctx.cell)
               / (len(kernel_ns) * ctx.peak["hbm_bytes_per_s"]))
    return 100.0 * least_s / (max(kernel_ns) / 1e9)
