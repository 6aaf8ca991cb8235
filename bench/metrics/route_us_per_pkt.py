"""Host microseconds per answered packet in the sharded fabric's RSS
dispatcher (``fabric.route``; program's span counter, delta over the
window): validation, header parse, flow-key hash, the route to a shard,
the fabric-wide count-min update and the per-shard split of each raw
submit.  The shards' own flow-engine spans are not in it.  A program
without a fabric keeps no such counter and reads as nothing."""

from benchlib.layers import us_per_packet


def read(ctx):
    return us_per_packet(ctx, ("fabric.route",))
