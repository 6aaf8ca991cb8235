"""Open loop at a fixed rate: packets fall due at the arrival times of a
Poisson process of ``rate`` packets per second, drawn from the seed.

Each iteration submits every packet due by now, at most ``max_burst``, in
``submit_*`` calls of ``chunk`` rows, and drains.  A packet's latency runs
from its due time to the return of the drain that answered it, so an
iteration that runs late delays the packets behind it and that delay is
counted.  Once ``seconds`` have passed, the loop answers every packet that
fell due in the window, the backlog too, and stops; the window ends with
the last answer.  ``notes`` logs how late the loop ran: the oldest due
packet's wait for its submit at each iteration, its longest, and its median
over the window's first and last quarter (a backlog that grows shows as a
last quarter far above the first)."""

import time

import numpy as np

from benchlib import gen
from benchlib.loop import Client


def due_times(seed: int, rate: float, seconds: float) -> np.ndarray:
    """Due times of the packets that fall due within ``seconds``."""
    n = int(rate * seconds * 1.1) + 1024
    due = np.cumsum(gen.stream_rng(seed, 5).exponential(1.0 / rate, n))
    if due[-1] < seconds:
        raise RuntimeError("arrival times ran out before the window's end")
    return due[: np.searchsorted(due, seconds)]


def run(srv, traffic, params, seconds, spans):
    due = due_times(params["seed"], params["rate"], seconds)
    client = Client(srv, traffic, params["chunk"], spans)
    answered_at = np.empty(due.shape[0])
    late = []                       # (iteration start, oldest packet's wait)
    t0 = time.perf_counter()
    while client.k < due.shape[0]:
        k = client.k
        now = time.perf_counter() - t0
        if due[k] > now:
            time.sleep(due[k] - now)
            continue
        n = min(int(np.searchsorted(due, now, "right")) - k,
                params["max_burst"])
        late.append((now, now - due[k]))
        client.iterate(n)
        answered_at[k: k + n] = time.perf_counter() - t0
    res = client.result(time.perf_counter() - t0)
    res.latency_s = answered_at - due
    at, wait = np.array(late).reshape(-1, 2).T
    res.notes = {"rate": params["rate"], "lateness_s": float(wait.max()),
                 "lateness_first_quarter_s": _median(wait[at < seconds / 4]),
                 "lateness_last_quarter_s": _median(
                     wait[at >= seconds * 3 / 4])}
    return res


def _median(x):
    return float(np.median(x)) if x.size else None
