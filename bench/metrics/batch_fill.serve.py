"""Mean fill of the service's flushes in the window: points mapped over
flushes over ``max_batch`` (``BatchedMapperService.stats()`` counters,
differenced across the window)."""


def read(ctx):
    c = ctx.counters
    if not c.get("flushes"):
        return None
    return 100.0 * c["points"] / c["flushes"] / c["max_batch"]
