"""Share of the traced window in which no operation ran on the device
(mean over the devices), in the EC cells."""


def read(ctx):
    return ctx.trace.idle_pct
