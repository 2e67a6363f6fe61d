"""Mean host time of one supervised serve dispatch
(``codes/engine.py::serve_dispatch_call``, ``serve_dispatch_seconds``):
the call from the host array's transfer until it returns the device
result, over the window."""


def read(ctx):
    count, total = ctx.telemetry.hist("serve_dispatch_seconds")
    return 1e3 * total / count if count else None
