"""Packets answered per second over the whole window (host clock)."""


def read(ctx):
    return ctx.res.answered / ctx.window_s
