"""Host microseconds per answered packet putting batches on the device
(program's span counters, deltas over the window): padding, the copy to
the device, the table snapshot and the program launch
(``ingress.dispatch``), and any compile inside the window
(``engine.compile``)."""

from benchlib.layers import us_per_packet


def read(ctx):
    return us_per_packet(ctx, ("ingress.dispatch", "engine.compile"))
