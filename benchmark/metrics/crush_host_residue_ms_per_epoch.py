"""Host time per epoch of the serial host-mapper walk over the lanes the
device rungs left unresolved (span ``crush.host_residue`` in
``crush/bulk.py::bulk_do_rule``, ``crush_host_residue_seconds``).
None where the program records no such span."""


def read(ctx):
    count, total = ctx.telemetry.hist("crush_host_residue_seconds")
    epochs = ctx.window.layer.get("epochs")
    if not count or not epochs:
        return None
    return 1e3 * total / epochs
