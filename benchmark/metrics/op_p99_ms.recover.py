"""The recovery cell's per-object p99 latency over the window, kept here
and not as an end-to-end metric: a closed loop keeps 64 objects in
flight, so one host stall of a second puts about 3% of a window's ops a
second late and moves the p99 by the stall's length (one run in six read
2.25 s against 1.12-1.23 s on a v5e)."""


def read(ctx):
    return ctx.window.e2e.get("op_p99_ms") if ctx.window.layer.get("ops") \
        else None
