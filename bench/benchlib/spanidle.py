"""Device idle time placed in the program's layer spans.

While a profiler runs, each layer span of the program
(``repro.obs.LayerSpans``) is a host event ``repro.<name>`` in the
``.xplane.pb``, on the same clock as the device's ``XLA Ops``.  This puts
each chip's idle time inside the traced window under the innermost span
open at the time, or under ``outside`` where none is, and names each of
the longest idle gaps by the span that covers most of it.

It reads the trace beside ``tracefile`` and changes nothing of what that
module reduces.  The window is the ``bench.window`` span where the trace
has one, else the extent of the device's ops.  Run it as
``python3 bench/spanidle.py <trace dir or .xplane.pb>``.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from . import tracefile

PROGRAM_PREFIX = "repro."
OUTSIDE = "outside"


def load_spans(path: str) -> Dict[str, List[Tuple[float, float]]]:
    """The program's layer spans of a trace: ``name → [(start_ns,
    end_ns)]``, the name without its ``repro.`` prefix."""
    from jax.profiler import ProfileData
    spans: Dict[str, List[Tuple[float, float]]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PROGRAM_PREFIX):
                    spans.setdefault(e.name[len(PROGRAM_PREFIX):], []).append(
                        (float(e.start_ns), float(e.start_ns + e.duration_ns)))
    return spans


def load(path: str):
    """``(devices, spans, window)`` of one ``.xplane.pb``: the device ops as
    ``tracefile.load_events`` gives them, the program's spans, and the
    window as ``(start_ns, end_ns)``."""
    devices, host = tracefile.load_events(path)
    if host.get("window"):
        window = host["window"][0]
    else:
        evs = [e for v in devices.values() for e in v]
        window = (min(e[1] for e in evs), max(e[2] for e in evs))
    return devices, load_spans(path), window


def _innermost(spans: dict):
    """The spans as disjoint segments labelled by the innermost span open
    in each: ``(names, seg)`` with ``names[0] == OUTSIDE`` and ``seg`` an
    ``(n, 3)`` array of ``(start, end, label)`` over the time some span is
    open.  Innermost is the open span that started last (on one thread,
    the most deeply nested one)."""
    names = [OUTSIDE]
    evs = []
    for name in sorted(spans):
        names.append(name)
        evs += [(s, t, len(names) - 1) for s, t in spans[name]]
    evs.sort(key=lambda e: (e[0], -e[1]))
    seg, stack, now = [], [], -np.inf
    for s, t, lab in evs:
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            if end > now:
                seg.append((now, end, top))
                now = end
        if stack and s > now:
            seg.append((now, s, stack[-1][1]))
        now = max(now, s)
        stack.append((t, lab))
    while stack:
        end, top = stack.pop()
        if end > now:
            seg.append((now, end, top))
            now = end
    return names, np.asarray(seg, np.float64).reshape(-1, 3)


def _by_span(gaps: np.ndarray, seg: np.ndarray, n_labels: int
             ) -> np.ndarray:
    """Seconds of each idle gap (rows of ``gaps``, disjoint and sorted)
    under each label of the disjoint sorted segments ``seg``: a
    ``(n_gaps, n_labels)`` array, time under no segment in column 0."""
    out = np.zeros((gaps.shape[0], n_labels))
    if gaps.size == 0:
        return out
    pts = np.unique(np.r_[gaps.ravel(), seg[:, :2].ravel()])
    mid = 0.5 * (pts[1:] + pts[:-1])
    gi = np.searchsorted(gaps[:, 0], mid, side="right") - 1
    inside = (gi >= 0) & (mid < gaps[np.maximum(gi, 0), 1])
    lab = np.zeros(mid.shape[0], np.int64)
    if seg.size:
        si = np.maximum(np.searchsorted(seg[:, 0], mid, side="right") - 1, 0)
        covered = (mid >= seg[si, 0]) & (mid < seg[si, 1])
        lab[covered] = seg[si[covered], 2].astype(np.int64)
    np.add.at(out, (gi[inside], lab[inside]),
              (pts[1:] - pts[:-1])[inside] * 1e-9)
    return out


def reduce(devices: dict, spans: dict, window, n_gaps: int = 10) -> dict:
    """Idle seconds by innermost open span, mean over the chips that ran
    something in the window (``idle_by_span_s``), and the ``n_gaps``
    longest idle gaps of any chip as ``(span, seconds)`` (``span_gaps``).
    The idle gaps are those ``tracefile.reduce`` attributes to the
    client."""
    w0, w1 = window
    names, seg = _innermost(spans)
    by_span = np.zeros(len(names))
    gaps, chips = [], 0
    for plane in sorted(devices):
        iv = np.asarray([(max(s, w0), min(t, w1))
                         for _, s, t in devices[plane] if t > w0 and s < w1])
        if not iv.size:
            continue
        chips += 1
        edges = np.r_[w0, tracefile._union(iv).ravel(), w1].reshape(-1, 2)
        idle = edges[edges[:, 1] > edges[:, 0]]
        per_gap = _by_span(idle, seg, len(names))
        by_span += per_gap.sum(axis=0)
        gaps += [(names[int(np.argmax(row))], (g1 - g0) * 1e-9)
                 for row, (g0, g1) in zip(per_gap, idle)]
    gaps.sort(key=lambda g: -g[1])
    return {"window_s": (w1 - w0) * 1e-9,
            "idle_by_span_s": {k: float(v) / max(chips, 1)
                               for k, v in zip(names, by_span)},
            "span_gaps": gaps[:n_gaps]}
