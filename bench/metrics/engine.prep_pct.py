"""Share of the slowest chip's device busy time spent outside the Mosaic
generation kernel, in percent: the window's state preparation (the LCG
root skip, and in faithful mode the xorshift start-state jump and the
chained tile jumps).

Per device: busy time less the time of the ops that ``trace.short_name``
marks ``tpu_custom_call``, over busy time.  The other ops' times are not
summed: the ops line nests a loop's body under its ``while``, so such a
sum would count the body twice.  The slowest chip is the one with the
most busy time.
"""
from bench import trace

KERNEL = "tpu_custom_call"


def read(ctx):
    devs = [d for d in ctx.trace.devices if d.busy_ns > 0]
    if not devs:
        return None
    d = max(devs, key=lambda d: d.busy_ns)
    kernel_ns = sum(t for n, t in d.op_ns.items()
                    if KERNEL in trace.short_name(n))
    return 100.0 * (d.busy_ns - kernel_ns) / d.busy_ns
