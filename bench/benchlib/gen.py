"""Traffic-generation library of the benchmark.

A traffic mix is a JSON file of parameters (``bench/traffic/<mix>.json``).
It names its generator, ``bench/generators/<generator>.py``, whose
``build(mix, cfg, seed, seconds)`` returns a :class:`Traffic`, and its
client loop, ``bench/loops/<loop.kind>.py``.  Both are found by name, so a
new arrival process or packet source is a new file.  The pieces they share
are here, each copied from the repository's own generators so that later
changes to the program cannot move the yardstick:

* :func:`raw_trace`: seeded 5-tuple flows, periodic and/or bursty trains
  (copied from ``repro.data.packets.raw_trace``; same bytes from the same
  generator state);
* :func:`wire_pool`: random feature packets of paper Table 1;
* :func:`warm_traffic`, :func:`sample_flows` and :func:`window_packets`.

Every draw comes from ``--seed`` through :func:`stream_rng`.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

from .wire import encode_raw_headers, encode_wire


def stream_rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per purpose, all from one ``--seed``."""
    return np.random.default_rng([stream, seed % (1 << 64)])


def raw_trace(rng: np.random.Generator, n_packets: int, *,
              n_flows: int = 256, model_ids: Sequence[int] = (1,),
              pattern: str = "mixed", base_period: int = 1024,
              jitter: int = 0, burst_len: int = 8,
              burst_gap: int = 16384, intra_gap: int = 16,
              fixed_length: bool = True):
    """Raw 5-tuple trace with bursty and/or periodic flows.

    ``"periodic"`` flows send every ``base_period`` ticks (random phase,
    optional ±``jitter``); ``"bursty"`` flows send trains of about
    ``burst_len`` packets ``intra_gap`` ticks apart, trains about
    ``burst_gap`` ticks apart; ``"mixed"`` makes even flows periodic and odd
    flows bursty.  Model ids are cyclic over the flows.  Returns
    ``(rows, flow)``: ``(n_packets, 21)`` uint8 rows sorted by arrival tick
    (stable) and each row's flow index."""
    if pattern not in ("periodic", "bursty", "mixed"):
        raise ValueError(f"unknown trace pattern: {pattern!r}")
    if n_flows <= 0 or n_packets <= 0:
        raise ValueError("n_flows and n_packets must be positive")
    per_flow = -(-n_packets // n_flows) + 2
    mids = np.asarray(model_ids, np.int64)

    flow_src = rng.integers(0, 2 ** 32, n_flows, np.uint32).astype(np.int64)
    flow_dst = rng.integers(0, 2 ** 32, n_flows, np.uint32).astype(np.int64)
    flow_sp = rng.integers(1024, 65536, n_flows).astype(np.int64)
    flow_dp = rng.integers(1, 1024, n_flows).astype(np.int64)
    flow_proto = rng.choice(np.asarray([6, 17], np.int64), n_flows)
    flow_mid = mids[np.arange(n_flows) % mids.size]
    flow_len = rng.integers(64, 1500, n_flows).astype(np.int64)

    all_ts, all_flow = [], []
    for i in range(n_flows):
        periodic = pattern == "periodic" or (pattern == "mixed"
                                             and i % 2 == 0)
        if periodic:
            phase = int(rng.integers(0, base_period))
            ts = phase + np.arange(per_flow, dtype=np.int64) * base_period
            if jitter:
                ts = ts + rng.integers(-jitter, jitter + 1, per_flow)
        else:
            iats = np.where(
                rng.random(per_flow) < 1.0 / max(burst_len, 1),
                rng.exponential(burst_gap, per_flow),
                float(intra_gap)).astype(np.int64)
            iats[0] = rng.integers(0, burst_gap)
            ts = np.cumsum(iats)
        all_ts.append(ts)
        all_flow.append(np.full(per_flow, i, np.int64))
    ts = np.concatenate(all_ts)
    flow = np.concatenate(all_flow)
    order = np.argsort(ts, kind="stable")[:n_packets]
    ts, flow = ts[order], flow[order]
    ts = np.minimum(ts, 2 ** 31 - 1)

    if fixed_length:
        length = flow_len[flow]
        bursty_pkt = np.zeros(flow.shape[0], bool)
        if pattern == "bursty":
            bursty_pkt[:] = True
        elif pattern == "mixed":
            bursty_pkt = flow % 2 == 1
        if bursty_pkt.any():
            length = length.copy()
            length[bursty_pkt] = rng.integers(
                64, 1500, int(bursty_pkt.sum()))
    else:
        length = rng.integers(64, 1500, flow.shape[0]).astype(np.int64)

    rows = encode_raw_headers(flow_src[flow], flow_dst[flow], flow_sp[flow],
                              flow_dp[flow], flow_proto[flow],
                              flow_mid[flow], ts, length)
    return rows, flow


def wire_pool(rng: np.random.Generator, *, n_rows: int,
              model_ids: Sequence[int], width: int, lo: int, hi: int,
              frac: int) -> np.ndarray:
    """``n_rows`` encapsulated feature packets: uniform tenant ids, every
    feature code uniform in ``[lo, hi)``."""
    mids = np.asarray(model_ids, np.int64)
    mid = mids[rng.integers(0, mids.size, n_rows)]
    x = rng.integers(lo, hi, (n_rows, width)).astype(np.int32)
    return encode_wire(mid, frac, x)


@dataclasses.dataclass
class Traffic:
    """Everything one run submits, built in set-up.

    ``rows`` are the window's packets in order (for ``cyclic`` traffic the
    pool the window cycles through).  ``setup_raw`` is raw traffic
    submitted in set-up (warm-up flows) and ``setup_wire`` encapsulated
    warm-up rows for the device lanes; ``sample`` holds the window
    positions whose answers the reference checks."""

    surface: str                      # "raw" | "wire"
    rows: np.ndarray
    cyclic: bool
    setup_raw: np.ndarray
    setup_wire: np.ndarray
    sample: np.ndarray

    def take(self, k: int, n: int) -> np.ndarray:
        """Window packets ``k .. k + n - 1``."""
        if not self.cyclic:
            if k + n > self.rows.shape[0]:
                raise RuntimeError(
                    f"traffic ran out: {k + n} packets wanted, "
                    f"{self.rows.shape[0]} generated")
            return self.rows[k: k + n]
        p = self.rows.shape[0]
        a = k % p
        if a + n <= p:
            return self.rows[a: a + n]
        return np.take(self.rows, np.arange(a, a + n) % p, axis=0)

    def row_at(self, idx: np.ndarray) -> np.ndarray:
        """Window packets at positions ``idx``."""
        return self.rows[idx % self.rows.shape[0] if self.cyclic else idx]




def tenant_ids(cfg: dict) -> list:
    """Model ids of the configuration's tenants, MLPs first."""
    ids = []
    for fam in ("mlp", "forest"):
        ids += list(cfg["tenants"].get(fam, {}).get("ids", []))
    return ids


def window_packets(mix: dict, seconds: float) -> int:
    """Packets a closed-loop window of ``seconds`` may take: the mix's
    ``sized_for_pps`` (about twice the measured rate) for the whole window,
    plus one burst."""
    return int(np.ceil(mix["sized_for_pps"] * seconds)) \
        + mix["loop"]["max_burst"]


def warm_traffic(mix: dict, cfg: dict, seed: int):
    """``(setup_raw, setup_wire)``: the warm-up traffic the mix's ``warm``
    asks for, which set-up submits before the window so that every batch
    shape and both device lanes have run once."""
    warm = mix.get("warm", {})
    rng = stream_rng(seed, 3)
    width = cfg["server"]["max_width"]
    ids = tenant_ids(cfg)
    setup_wire = np.zeros((0, 7 + 4 * width), np.uint8)
    if warm.get("wire_rows"):
        setup_wire = wire_pool(rng, n_rows=warm["wire_rows"], model_ids=ids,
                               width=width, lo=-(1 << 20), hi=1 << 20,
                               frac=cfg["server"]["frac_bits"])
    setup_raw = np.zeros((0, 21), np.uint8)
    if warm.get("raw_packets"):
        setup_raw, _ = raw_trace(rng, warm["raw_packets"],
                                 n_flows=warm["raw_flows"], model_ids=ids,
                                 **mix.get("params", {}))
    return setup_raw, setup_wire


def sample_flows(rng, flow: np.ndarray, n_sample: int) -> np.ndarray:
    """Positions of every packet of ``n_sample`` flows drawn from the seed,
    the flow with the most packets among them."""
    counts = np.bincount(flow)
    present = np.nonzero(counts)[0]
    pick = rng.choice(present, min(n_sample, present.size), replace=False)
    pick = np.union1d(pick, [int(np.argmax(counts))])
    return np.nonzero(np.isin(flow, pick))[0]
