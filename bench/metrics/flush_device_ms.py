"""Device time of the fused flush program (``server_flush_step`` or its
sharded twin) per flush, averaged over the chips the cell uses."""

PROGRAM = r"^jit_server_flush_step"


def read(ctx):
    flushes = len(ctx.window.flush_s)
    if ctx.trace is None or not flushes:
        return None
    secs, calls = ctx.trace.module_time(PROGRAM)
    if not calls:
        return None
    return secs / ctx.chips / flushes * 1e3
