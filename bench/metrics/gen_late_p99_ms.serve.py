"""How late the load generator submitted: the 99th percentile of submit
time minus due time over the window's requests (host clock).  A starved
generator shows here rather than as a fast server."""


def read(ctx):
    return ctx.counters.get("gen_late_p99_ms")
