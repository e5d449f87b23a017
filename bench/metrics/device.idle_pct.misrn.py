"""Share of the traced window in which no operation ran on the device,
in percent, averaged over the chips used (MISRN cells)."""


def read(ctx):
    return ctx.trace.idle_pct()
