"""Host microseconds per answered packet in egress (program's span
counters, deltas over the window): the egress encode and its echo check
(``egress.encode``), the result-cache insert (``egress.cache_insert``),
and resolving tickets and assembling the ordered answers
(``egress.resolve``)."""

from benchlib.layers import us_per_packet


def read(ctx):
    return us_per_packet(ctx, ("egress.encode", "egress.cache_insert",
                               "egress.resolve"))
