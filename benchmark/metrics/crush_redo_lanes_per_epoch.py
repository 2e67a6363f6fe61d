"""Lanes per epoch that the bulk CRUSH evaluator re-dispatched at a deeper
rung (``crush_redo_lanes``, counted in ``crush/bulk.py::bulk_do_rule``
for each rung >= 1 that ran).  0 where no deeper rung ran; None where
the program records no ``crush.rung`` span."""


def read(ctx):
    count, _ = ctx.telemetry.hist("crush_rung_seconds")
    epochs = ctx.window.layer.get("epochs")
    if not count or not epochs:
        return None
    return ctx.telemetry.total("crush_redo_lanes") / epochs
