"""Host time per dispatch over the window, in ``serve/batcher.py``:
copying the ready device result to the host (span ``serve.d2h``).  The self time of the span (``serve_d2h_seconds``),
summed, over the window's ``serve_dispatches``.  None where the program
records no such span."""


def read(ctx):
    count, total = ctx.telemetry.hist("serve_d2h_seconds")
    dispatches = ctx.telemetry.total("serve_dispatches")
    if not count or not dispatches:
        return None
    return 1e3 * total / dispatches
