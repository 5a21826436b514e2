"""Requests per micro-batcher flush over the window (served / flushes)."""


def read(ctx):
    f = ctx.counters.get("flushes")
    return ctx.counters["served"] / f if f else None
