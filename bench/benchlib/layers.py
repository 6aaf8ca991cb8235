"""Per-layer host time read from the program's layer-span counters.

The program adds each layer span's self time (its duration less the spans
nested inside it) to a registry counter named after the span:
``flow.lookup`` → ``flow_lookup_seconds_total``.  A layer's reading is the
window's delta of its spans' counters over the answered packets.
"""


def us_per_packet(ctx, spans):
    """Host microseconds per answered packet in ``spans``, or ``None``
    where the program keeps none of their counters."""
    c = ctx.counters
    names = [s.replace(".", "_") + "_seconds_total" for s in spans]
    if not ctx.res.answered or not any(n in c for n in names):
        return None
    return sum(c.get(n, 0.0) for n in names) / ctx.res.answered * 1e6
