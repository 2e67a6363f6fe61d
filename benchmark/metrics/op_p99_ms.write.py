"""The write cell's per-object p99 latency over the window, kept here and
not as an end-to-end metric: at the closed loop's full load the tail
moves in whole dispatch rounds (about 410 or 510 ms on a v5e), so one
slow round decides it."""


def read(ctx):
    return ctx.window.e2e.get("op_p99_ms") if ctx.window.layer.get("ops") \
        else None
