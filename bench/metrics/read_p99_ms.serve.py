"""The 99th percentile of the window's read latencies, from each
request's due time (host clock), in the traced run.  Above the knee the
queue grows all through the window, so the tail swings with the smallest
change in what the service completes; it stands beside the judged
``read_pts_s`` without a bound."""


def read(ctx):
    return ctx.run.e2e.get("read_p99_ms")
