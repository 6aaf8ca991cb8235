"""Seconds from process start to the start of the window: imports, the
server and its tenants, traffic generation, compilation (or loading it from
the cache) and warm-up traffic."""


def read(ctx):
    return ctx.setup_s
