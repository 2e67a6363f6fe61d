"""Device time of the bulk CRUSH rule programs (``crush/bulk.py``
builds them as ``jax.jit(vf)`` of the vmapped rule ``fn``: ``jit_fn``)
in the traced window, per epoch completed in it."""

CRUSH_PROGRAMS = ("jit_fn",)


def read(ctx):
    seconds, _ = ctx.trace.program_seconds(CRUSH_PROGRAMS)
    epochs = ctx.window.layer.get("epochs")
    if not seconds or not epochs:
        return None
    return 1e3 * seconds / epochs
