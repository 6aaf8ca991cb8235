"""Host microseconds per answered packet in the flow engine's front half
(program's span counters, deltas over the window): header validation,
parse and key hashing (``flow.parse``), the flow-table probe and insert
(``flow.lookup``), and table expiry, compaction and eviction
(``flow.compact``)."""

from benchlib.layers import us_per_packet


def read(ctx):
    return us_per_packet(ctx, ("flow.parse", "flow.lookup", "flow.compact"))
