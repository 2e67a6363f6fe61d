"""Mean time a served request spent in a bucket over the window: from
the poll that drained it from the admission queue to its batch's
dispatch (``serve_batch_wait_seconds``).  The rest of
``serve_queue_wait_ms`` is time in the admission queue."""


def read(ctx):
    count, total = ctx.telemetry.hist("serve_batch_wait_seconds")
    return 1e3 * total / count if count else None
