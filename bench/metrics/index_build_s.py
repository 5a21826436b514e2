"""Host-clock seconds of ``engine.compile`` (the shred index build) up to
``block_until_ready`` of the index, in set-up."""


def read(ctx):
    return ctx.counters.get("index_build_s")
