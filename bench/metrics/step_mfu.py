"""The whole step's share of the chips' peak: the larger of the required
FLOPs per second over the bf16 peak and the required HBM bytes per second
over the HBM peak, at the window's upload rate. The FLOPs are the clients'
training (three forward passes); the bytes are each upload's client read
and write, its data, and its share of a flush (``bench/work.py``)."""


def read(ctx):
    rate = ctx.uploads_per_s
    if not rate:
        return None
    flops = ctx.work["flops"] * rate / (ctx.chips * ctx.peak["flops_per_s"])
    hbm = ctx.work["bytes"] * rate / (ctx.chips * ctx.peak["hbm_bytes_per_s"])
    return max(flops, hbm) * 100.0
