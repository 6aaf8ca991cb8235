"""Host microseconds per answered packet inside the client's
``drain_packets`` calls: flushing the last batch, waiting on and retiring
device batches, egress encode, cache inserts and (on a fabric) the ordered
merge across shards."""


def read(ctx):
    if not ctx.res.answered:
        return None
    return ctx.res.span_s["drain"] / ctx.res.answered * 1e6
