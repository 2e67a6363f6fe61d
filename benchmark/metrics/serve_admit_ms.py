"""Host time per dispatch over the window, in ``serve/batcher.py``:
``ContinuousBatcher.admit``, classifying the requests a poll drained into
buckets (span ``serve.admit``; a full-bucket fire nested in it is its
child and left out).  The self time of the span (``serve_admit_seconds``),
summed, over the window's ``serve_dispatches``.  None where the program
records no such span."""


def read(ctx):
    count, total = ctx.telemetry.hist("serve_admit_seconds")
    dispatches = ctx.telemetry.total("serve_dispatches")
    if not count or not dispatches:
        return None
    return 1e3 * total / dispatches
