"""Device time per flush: the traced window's busy time (union of device
op intervals, mean over chips) over the flushes the service ran in it."""


def read(ctx):
    flushes = ctx.counters.get("flushes")
    if not flushes:
        return None
    return 1e3 * ctx.reduced.busy_s / flushes
