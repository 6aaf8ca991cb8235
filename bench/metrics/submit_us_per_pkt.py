"""Host microseconds per answered packet inside the client's ``submit_*``
calls: flow lookup and register update, FeatureSpec gather, dedup, cache
probe and staging, plus any dispatch that staging triggers."""


def read(ctx):
    if not ctx.res.answered:
        return None
    return ctx.res.span_s["submit"] / ctx.res.answered * 1e6
