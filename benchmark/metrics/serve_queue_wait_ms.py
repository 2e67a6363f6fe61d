"""Mean wait of a served request between admission and its batch's
dispatch, over the window (``serve_queue_wait_seconds``)."""


def read(ctx):
    count, total = ctx.telemetry.hist("serve_queue_wait_seconds")
    return 1e3 * total / count if count else None
