"""Share of the HBM roofline the EC programs reached in the traced
window: the least time the stripes returned in the window could take,
(least bytes) / (819 GB/s per chip), over the device time of the EC
serve programs (``jit_raw``: ``codes/engine.py`` builds them as
``jax.jit(raw)``) summed over the devices.

The least bytes come from each op's parameters (encode: k chunks read
and m written per stripe; clay single-chunk repair: d*C/(d-k+1) read
and C written), so they read the same whatever implements the op.
GF(2^8) work has no FLOP peak that applies; the HBM bound alone is
used."""

EC_PROGRAMS = ("jit_raw",)


def read(ctx):
    seconds, _ = ctx.trace.program_seconds(EC_PROGRAMS)
    least = ctx.window.layer.get("least_bytes")
    if not seconds or not least:
        return None
    return 100.0 * least / ctx.peaks["hbm_bytes_per_s"] / seconds
