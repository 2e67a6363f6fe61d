"""Stripes the batcher put into each device dispatch over the window
(``serve_stripes`` / ``serve_dispatches``)."""


def read(ctx):
    dispatches = ctx.telemetry.total("serve_dispatches")
    return ctx.telemetry.total("serve_stripes") / dispatches \
        if dispatches else None
