"""Host time per dispatch over the window, in ``serve/batcher.py``:
stacking a batch's payloads into the rung-shaped host array (span
``serve.pack``).  The self time of the span (``serve_pack_seconds``),
summed, over the window's ``serve_dispatches``.  None where the program
records no such span."""


def read(ctx):
    count, total = ctx.telemetry.hist("serve_pack_seconds")
    dispatches = ctx.telemetry.total("serve_dispatches")
    if not count or not dispatches:
        return None
    return 1e3 * total / dispatches
