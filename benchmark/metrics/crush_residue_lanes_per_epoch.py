"""Lanes per epoch left to the serial host mapper after the device rungs
(``crush_host_residue_lanes``, the ``n_fallback`` of
``crush/bulk.py::bulk_do_rule``).  None where the program records no
``crush.host_residue`` span."""


def read(ctx):
    count, _ = ctx.telemetry.hist("crush_host_residue_seconds")
    epochs = ctx.window.layer.get("epochs")
    if not count or not epochs:
        return None
    return ctx.telemetry.total("crush_host_residue_lanes") / epochs
