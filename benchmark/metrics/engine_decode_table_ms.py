"""Host time per dispatch to build a fire's decode coefficient operand
(span ``engine.decode_table`` in ``codes/engine.py::decode_table``:
decode rows looked up, or inverted on a miss, for each pattern of the
fire and gathered per stripe): ``engine_decode_table_seconds`` sum over
the window's ``serve_dispatches``.  None where the program records no
such span."""


def read(ctx):
    count, total = ctx.telemetry.hist("engine_decode_table_seconds")
    dispatches = ctx.telemetry.total("serve_dispatches")
    if not count or not dispatches:
        return None
    return 1e3 * total / dispatches
