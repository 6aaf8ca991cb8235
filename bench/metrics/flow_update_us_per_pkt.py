"""Host microseconds per answered packet in the flow engine's register
and count-min update (``flow.update``) and the FeatureSpec gather
(``flow.gather``) (program's span counters, deltas over the window)."""

from benchlib.layers import us_per_packet


def read(ctx):
    return us_per_packet(ctx, ("flow.update", "flow.gather"))
