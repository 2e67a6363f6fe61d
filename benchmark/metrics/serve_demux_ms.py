"""Host time per dispatch over the window, in ``serve/batcher.py``:
slicing a batch's output into per-request results and observing their
waits (span ``serve.demux``).  The self time of the span (``serve_demux_seconds``),
summed, over the window's ``serve_dispatches``.  None where the program
records no such span."""


def read(ctx):
    count, total = ctx.telemetry.hist("serve_demux_seconds")
    dispatches = ctx.telemetry.total("serve_dispatches")
    if not count or not dispatches:
        return None
    return 1e3 * total / dispatches
