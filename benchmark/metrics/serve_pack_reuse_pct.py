"""Share of the window's dense fires in ``serve/batcher.py`` that packed
into a staging buffer already allocated (``serve_pack_buffers
{outcome=reused}`` over all outcomes).  None where the program counts
no staging buffers."""


def read(ctx):
    total = ctx.telemetry.total("serve_pack_buffers")
    if not total:
        return None
    reused = sum(v for k, v in ctx.telemetry.d.items()
                 if k.startswith("serve_pack_buffers{")
                 and "outcome=reused" in k)
    return 100.0 * reused / total
