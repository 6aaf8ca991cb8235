"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's
device numbers.

* The traced window is the host span ``bench.window``.
* A chip's busy time is the union of its ``XLA Ops`` events inside the
  window; ``busy_s`` is the mean over the chips that ran anything.
* Per-op device seconds (summed over chips, clipped to the window) feed the
  kernel readers and the ``device_ops`` breakdown.
* Idle gaps on each chip are attributed to what the benchmark's client was
  doing at the time: inside ``bench.submit``, inside ``bench.drain``, or in
  its own loop (``client``).
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Tuple

import numpy as np

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
SPAN_PREFIX = "bench."


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge ``(n, 2)`` intervals into disjoint sorted ones."""
    if iv.size == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.r_[True, iv[1:, 0] > ends[:-1]]
    starts = iv[new, 0]
    idx = np.nonzero(new)[0]
    stops = ends[np.r_[idx[1:] - 1, iv.shape[0] - 1]]
    return np.stack([starts, stops], axis=1)


def _overlap(a0: float, a1: float, iv: np.ndarray) -> float:
    if iv.size == 0:
        return 0.0
    return float(np.clip(np.minimum(iv[:, 1], a1) - np.maximum(iv[:, 0], a0),
                         0, None).sum())


def op_name(event_name: str) -> str:
    """The HLO instruction an ``XLA Ops`` event ran, without its ordinal:
    ``"%fixedpoint_mlp_pallas.1 = s32[...] custom-call(...)"`` gives
    ``"fixedpoint_mlp_pallas"``."""
    name = event_name.split(" = ", 1)[0].lstrip("%")
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def load_events(path: str):
    """``(devices, host)``: per device plane the ``XLA Ops`` events as
    ``(op name, start_ns, end_ns)``; the benchmark's host spans by kind."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    host: Dict[str, List[Tuple[float, float]]] = {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                evs = devices.setdefault(plane.name, [])
                for e in line.events:
                    evs.append((op_name(e.name), float(e.start_ns),
                                float(e.start_ns + e.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host.setdefault(e.name[len(SPAN_PREFIX):], []).append(
                            (float(e.start_ns),
                             float(e.start_ns + e.duration_ns)))
    return devices, host


def reduce(devices: dict, host: dict, n_gaps: int = 10) -> dict:
    """Device busy/idle, per-op seconds and idle attribution over the
    ``bench.window`` span."""
    if not host.get("window"):
        raise ValueError("trace holds no bench.window span")
    w0, w1 = host["window"][0]
    spans = {k: _union(np.asarray(v, np.float64).reshape(-1, 2))
             for k, v in host.items() if k in ("submit", "drain")}
    busy, op_s, op_n = [], {}, {}
    idle_by = {"submit": 0.0, "drain": 0.0, "client": 0.0}
    gaps = []
    for plane in sorted(devices):
        evs = [e for e in devices[plane] if e[2] > w0 and e[1] < w1]
        if not evs:
            continue
        iv = np.asarray([(max(s, w0), min(t, w1)) for _, s, t in evs])
        for (name, _, _), (s, t) in zip(evs, iv):
            op_s[name] = op_s.get(name, 0.0) + (t - s) * 1e-9
            op_n[name] = op_n.get(name, 0) + 1
        u = _union(iv)
        busy.append(float((u[:, 1] - u[:, 0]).sum()) * 1e-9)
        edges = np.r_[w0, u.ravel(), w1].reshape(-1, 2)
        for g0, g1 in edges:
            if g1 <= g0:
                continue
            part = {k: _overlap(g0, g1, spans.get(k, np.zeros((0, 2))))
                    for k in ("submit", "drain")}
            part["client"] = max((g1 - g0) - sum(part.values()), 0.0)
            for k, v in part.items():
                idle_by[k] += v * 1e-9
            gaps.append((max(part, key=part.get), (g1 - g0) * 1e-9))
    n_chips = max(len(busy), 1)
    gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": float(np.mean(busy)) if busy else 0.0,
        "chips_busy": len(busy),
        "op_seconds": op_s,
        "op_counts": op_n,
        "idle_by_activity_s": {k: v / n_chips for k, v in idle_by.items()},
        "longest_gaps": gaps[:n_gaps],
    }


def breakdown(red: dict) -> dict:
    """The result line's ``breakdown``: the ten device ops that took most
    time (seconds summed over chips) and the idle time by what the client
    was doing (mean over chips), then the longest single gaps."""
    ops = sorted(red["op_seconds"].items(), key=lambda kv: -kv[1])[:10]
    idle = [[f"idle_in_{k}", v] for k, v in
            sorted(red["idle_by_activity_s"].items(), key=lambda kv: -kv[1])]
    idle += [[f"gap_in_{k}", s] for k, s in red["longest_gaps"]]
    return {"device_ops": [[n, s] for n, s in ops], "idle_gaps": idle[:10]}
