"""Percent of the HBM roofline of a batched draw call: the traffic's
``batch`` draws, each moving ``roofline_bytes.draw_bytes`` at the window's
mean draw size, over the device busy time of one ``draw`` span (one call).
None where the traffic is not a batched draw."""
from bench.metrics import roofline_bytes


def read(ctx):
    b = ctx.cell.traffic.get("batch")
    t = ctx.trace.span_device_s("draw")
    k = ctx.counters.get("mean_count")
    if not b or not t or k is None:
        return None
    cfg = ctx.cell.config
    nbytes = int(b) * roofline_bytes.draw_bytes(cfg["tree"], ctx.tables,
                                                cfg["prob_var"], k)
    return roofline_bytes.share(nbytes, t, ctx.peaks)
