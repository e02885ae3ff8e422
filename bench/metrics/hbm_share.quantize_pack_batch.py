"""The flush's broadcast encode kernel (``kernels/qsgd``
``qsgd_quantize_pack_batch``) as a share of the HBM roofline: the bytes
one encode of the n-value broadcast must move (n f32 read, the packed
message written) times the flushes, over the kernel's device time summed
over chips, over the per-chip HBM peak."""

from bench import work

KERNEL = r"^%qsgd_quantize_pack_batch\."


def read(ctx):
    flushes = len(ctx.window.flush_s)
    if ctx.trace is None or not flushes:
        return None
    secs, calls = ctx.trace.op_time(KERNEL)
    if not calls or secs <= 0:
        return None
    need = flushes * work.quantize_pack_bytes(ctx.d, ctx.sbits)
    return need / secs / ctx.peak["hbm_bytes_per_s"] * 100.0
