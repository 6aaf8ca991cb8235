"""Host microseconds per answered packet in the sharded fabric's merge
(``fabric.merge``; program's span counter, delta over the window): the
interleave of the shards' drained answers back into global submission
order, after the shards' own drains.  A program without a fabric keeps no
such counter and reads as nothing."""

from benchlib.layers import us_per_packet


def read(ctx):
    return us_per_packet(ctx, ("fabric.merge",))
