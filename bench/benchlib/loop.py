"""The client: a poll-mode receive loop over the public serving surface.

One iteration (:meth:`Client.iterate`) hands the server the next ``n``
packets in ``submit_*`` calls of ``chunk`` rows, then calls
``drain_packets()``, which flushes and returns every answer in submission
order.  A packet is answered when its slot holds an egress row; an error
slot, or a row never returned, counts as failed.

When packets are due, and so when an iteration runs and how long the
window lasts, is the loop's: a file ``bench/loops/<kind>.py`` named by the
mix's ``loop.kind``, whose ``run(srv, traffic, params, seconds, spans)``
drives :class:`Client` and returns its :class:`WindowResult`.  ``params``
is the mix's ``loop`` with the run's ``seed`` added.

The client's own host time in ``submit_*`` and ``drain_packets`` is kept
as spans (and written into the profiler's trace when one is running).
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np


@dataclasses.dataclass
class WindowResult:
    attempted: int = 0
    answered: int = 0
    failed: int = 0
    seconds: float = 0.0
    iterations: int = 0
    longest_iteration_s: float = 0.0
    span_s: dict = dataclasses.field(
        default_factory=lambda: {"submit": 0.0, "drain": 0.0})
    sample_pos: np.ndarray = None
    sample_rows: np.ndarray = None
    latency_s: np.ndarray = None      # per answered packet, where a loop
                                      # keeps due times
    notes: dict = dataclasses.field(default_factory=dict)


class Spans:
    """Host seconds inside the client's calls, by kind; with ``traced``
    each call is also a ``bench.<kind>`` span in the profiler's trace."""

    def __init__(self, traced: bool):
        self.total = {"submit": 0.0, "drain": 0.0}
        if traced:
            from jax.profiler import TraceAnnotation
            self._ann = TraceAnnotation
        else:
            self._ann = None

    def call(self, kind: str, fn, *args):
        t0 = time.perf_counter()
        if self._ann is None:
            out = fn(*args)
        else:
            with self._ann(f"bench.{kind}"):
                out = fn(*args)
        self.total[kind] += time.perf_counter() - t0
        return out


def _answered(out: list) -> int:
    if {type(r) for r in out} <= {np.ndarray}:
        return len(out)
    return sum(1 for r in out if isinstance(r, np.ndarray))


class Client:
    """Submits the traffic's window packets in order and keeps the counts,
    the spans and the egress rows of the sampled packets."""

    def __init__(self, srv, traffic, chunk: int, spans: Spans):
        self.srv = srv
        self.traffic = traffic
        self.chunk = chunk
        self.spans = spans
        self.submit = (srv.submit_raw if traffic.surface == "raw"
                       else srv.submit_packets)
        self.res = WindowResult()
        self.k = 0                      # window packets submitted so far
        self._pos, self._rows = [], []

    def iterate(self, n: int) -> None:
        """Submit the next ``n`` window packets, then drain."""
        t0 = time.perf_counter()
        k, rows = self.k, self.traffic.take(self.k, n)
        for i in range(0, n, self.chunk):
            self.spans.call("submit", self.submit, rows[i: i + self.chunk])
        out = self.spans.call("drain", self.srv.drain_packets)
        got = _answered(out)
        self.res.answered += got
        self.res.failed += n - got
        sample = self.traffic.sample
        a, b = np.searchsorted(sample, [k, k + n])
        for p in sample[a:b].tolist():
            r = out[p - k] if p - k < len(out) else None
            if isinstance(r, np.ndarray):
                self._pos.append(p)
                self._rows.append(r)
        self.k += n
        self.res.iterations += 1
        self.res.longest_iteration_s = max(self.res.longest_iteration_s,
                                           time.perf_counter() - t0)

    def result(self, seconds: float) -> WindowResult:
        res = self.res
        res.seconds = seconds
        res.attempted = self.k
        res.span_s = self.spans.total
        res.sample_pos = np.asarray(self._pos, np.int64)
        res.sample_rows = (np.stack(self._rows) if self._rows
                           else np.zeros((0, 0), np.uint8))
        return res


def submit_all(srv, rows: np.ndarray, surface: str, chunk: int) -> int:
    """Set-up traffic: submit ``rows`` in chunks, drain, and return how
    many answers were not egress rows."""
    if rows.shape[0] == 0:
        return 0
    submit = srv.submit_raw if surface == "raw" else srv.submit_packets
    for i in range(0, rows.shape[0], chunk):
        submit(rows[i: i + chunk])
    out = srv.drain_packets()
    return rows.shape[0] - _answered(out)
