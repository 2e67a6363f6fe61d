"""Host wall time of the bulk CRUSH device rungs per epoch: each rung's
block dispatches from the H2D of the inputs to the fetched results
(span ``crush.rung`` in ``crush/bulk.py::bulk_do_rule``,
``crush_rung_seconds``).  Compare with ``crush_device_ms_per_epoch``.
None where the program records no such span."""


def read(ctx):
    count, total = ctx.telemetry.hist("crush_rung_seconds")
    epochs = ctx.window.layer.get("epochs")
    if not count or not epochs:
        return None
    return 1e3 * total / epochs
