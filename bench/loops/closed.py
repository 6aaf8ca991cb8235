"""Closed loop: every packet is due at once.  Each iteration submits
``max_burst`` packets in ``submit_*`` calls of ``chunk`` rows and drains;
the loop runs until ``seconds`` have passed, and the answered rate is
taken over the whole window."""

import time

from benchlib.loop import Client


def run(srv, traffic, params, seconds, spans):
    client = Client(srv, traffic, params["chunk"], spans)
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        client.iterate(params["max_burst"])
    return client.result(time.perf_counter() - t0)
