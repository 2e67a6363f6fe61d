"""Distinct erasure patterns in one decode/repair fire of
``serve/batcher.py``, over the window (histogram
``serve_dispatch_patterns``, sum over count).  A per-pattern bucket
fires one pattern; a pattern-generic decode bucket many.  None where
the program records no such histogram."""


def read(ctx):
    count, total = ctx.telemetry.hist("serve_dispatch_patterns")
    return total / count if count else None
