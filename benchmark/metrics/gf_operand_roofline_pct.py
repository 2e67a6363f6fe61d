"""Share of the HBM roofline the pattern-generic decode programs reached
in the traced window: the least time the stripes returned in the
window could take, (least bytes) / (819 GB/s per chip), over the device
time of those programs alone (``jit_raw_operand``: ``codes/engine.py::
serve_decode_generic`` builds them as ``jax.jit(raw_operand)``, around
the operand-coefficient GF(2^8) kernel), summed over the devices.

The least bytes come from each op's parameters, in
``drivers/ec_serve.py``: a decode reads k chunks and writes the lost
ones, (k + 2) * C a stripe that lost two shards.  None where no such
program ran (a program that decodes per pattern)."""

OPERAND_PROGRAMS = ("jit_raw_operand",)


def read(ctx):
    seconds, _ = ctx.trace.program_seconds(OPERAND_PROGRAMS)
    least = ctx.window.layer.get("least_bytes")
    if not seconds or not least:
        return None
    return 100.0 * least / ctx.peaks["hbm_bytes_per_s"] / seconds
