"""Host time per dispatch over the window, in ``serve/batcher.py``:
waiting for the device result after the engine call returned (span
``serve.wait``: ``jax.block_until_ready``).  The self time of the span (``serve_device_wait_seconds``),
summed, over the window's ``serve_dispatches``.  None where the program
records no such span."""


def read(ctx):
    count, total = ctx.telemetry.hist("serve_device_wait_seconds")
    dispatches = ctx.telemetry.total("serve_dispatches")
    if not count or not dispatches:
        return None
    return 1e3 * total / dispatches
