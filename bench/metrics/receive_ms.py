"""Mean host time of the ``receive`` calls that do not flush: the
staleness bookkeeping, the traffic meter and the buffer add (and, for a
tier's upload, its eager decode)."""


def read(ctx):
    r = ctx.window.receive_s
    return sum(r) / len(r) * 1e3 if r else None
