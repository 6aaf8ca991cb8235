"""Share of the window's packets answered without a device row of their
own, in percent: result-cache hits and packets coalesced onto an identical
row already in flight, over the packets the ingress took (the program's
counters, deltas over the window)."""


def read(ctx):
    c = ctx.counters
    packets = c.get("ingress_packets_total", 0.0)
    if not packets:
        return None
    short = (c.get("ingress_cache_hits_total", 0.0)
             + c.get("ingress_coalesced_total", 0.0))
    return 100.0 * short / packets
