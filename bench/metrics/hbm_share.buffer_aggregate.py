"""The flush's fused dequantize-accumulate kernel (``kernels/buffer_agg``)
as a share of the HBM roofline: the bytes it must move per flush (K packed
uploads read, one f32 vector written) times the flushes, over the
kernel's device time summed over chips, over the per-chip HBM peak."""

from bench import work

KERNEL = r"^%buffer_aggregate\."


def read(ctx):
    flushes = len(ctx.window.flush_s)
    if ctx.trace is None or not flushes:
        return None
    secs, calls = ctx.trace.op_time(KERNEL)
    if not calls or secs <= 0:
        return None
    need = flushes * work.buffer_aggregate_bytes(ctx.d, ctx.bits, ctx.k)
    return need / secs / ctx.peak["hbm_bytes_per_s"] * 100.0
