"""Host microseconds per answered packet blocked on the device at retire:
the wait for a batch's result and its copy back to the host
(``ingress.device_wait``; program's span counter, delta over the
window)."""

from benchlib.layers import us_per_packet


def read(ctx):
    return us_per_packet(ctx, ("ingress.device_wait",))
