"""Device time of the client-step program (the jitted
``cohort_train_encode_step``, named ``jit_step`` on the device) per
trained upload, averaged over the chips the cell uses."""

PROGRAM = r"^jit_step\b"


def read(ctx):
    if ctx.trace is None or not ctx.window.trained:
        return None
    secs, calls = ctx.trace.module_time(PROGRAM)
    if not calls:
        return None
    return secs / ctx.chips / ctx.window.trained * 1e3
