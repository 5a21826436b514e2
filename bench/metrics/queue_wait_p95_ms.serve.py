"""95th percentile (nearest rank) of the wait from a request's scheduled
arrival to the start of the flush that served it, in milliseconds."""


def read(ctx):
    w = ctx.counters.get("queue_wait_p95_s")
    return None if w is None else 1e3 * w
