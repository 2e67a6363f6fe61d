"""Host time per epoch of the up-set derivation after stage 1 (upmap
overrides and the numpy ``_bulk_up_from_raw``; span
``crush.up_derive`` in ``crush/osdmap.py::pg_to_up_bulk``,
``crush_up_derive_seconds``).
None where the program records no such span."""


def read(ctx):
    count, total = ctx.telemetry.hist("crush_up_derive_seconds")
    epochs = ctx.window.layer.get("epochs")
    if not count or not epochs:
        return None
    return 1e3 * total / epochs
