"""Host microseconds per answered packet in the ingress pipeline's own
work (program's span counters, deltas over the window): tickets, key
encode and hashing, cache probe, dedup and pending-window coalescing
(``ingress.ingest``), the wire parse (``ingress.parse``), staging
(``ingress.stage``) and result-cache flushes and compactions
(``cache.compact``)."""

from benchlib.layers import us_per_packet


def read(ctx):
    return us_per_packet(ctx, ("ingress.ingest", "ingress.parse",
                               "ingress.stage", "cache.compact"))
