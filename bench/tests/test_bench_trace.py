"""Trace reduction: hand-made events, and a small trace recorded on a
TPU v5e (``data/v5e_wire.xplane.pb``: a short traced window of the
``mlp16-wire.unique`` cell) checked against a brute-force recount."""

from __future__ import annotations

from pathlib import Path

import numpy as np
import pytest

from benchlib import tracefile, work

CHIP_TRACE = Path(__file__).resolve().parent / "data" / "v5e_wire.xplane.pb"


def test_op_name_strips_the_hlo_text_and_ordinal():
    assert tracefile.op_name(
        "%fixedpoint_mlp_pallas.1 = s32[2048,32]{1,0} custom-call(...)"
    ) == "fixedpoint_mlp_pallas"
    assert tracefile.op_name("%fusion = s32[2048]{0} fusion(...)") == "fusion"
    assert tracefile.op_name("%copy-done.3 = s32[16]") == "copy-done"


def test_reduce_hand_made_events():
    ms = 1e6  # ns
    devices = {
        "/device:TPU:0": [("a", 1 * ms, 3 * ms), ("b", 2 * ms, 4 * ms),
                          ("a", 8 * ms, 9 * ms), ("a", 12 * ms, 15 * ms)],
        "/device:TPU:1": [("a", 0 * ms, 2 * ms)],
    }
    host = {"window": [(0.0, 10 * ms)],
            "submit": [(4 * ms, 8 * ms)],
            "drain": [(9 * ms, 10 * ms)]}
    red = tracefile.reduce(devices, host)
    assert red["window_s"] == pytest.approx(0.010)
    # chip 0 busy [1,4] + [8,9] = 4 ms (the event past 10 ms is outside);
    # chip 1 busy 2 ms
    assert red["busy_s"] == pytest.approx((0.004 + 0.002) / 2)
    assert red["op_seconds"]["a"] == pytest.approx(0.002 + 0.001 + 0.002)
    assert red["op_seconds"]["b"] == pytest.approx(0.002)
    assert red["op_counts"] == {"a": 3, "b": 1}
    # chip 0 idle: [0,1] client, [4,8] submit, [9,10] drain;
    # chip 1 idle: one gap [2,10], 4 ms of it in submit, 1 ms in drain
    idle = red["idle_by_activity_s"]
    assert idle["submit"] == pytest.approx((0.004 + 0.004) / 2)
    assert idle["drain"] == pytest.approx((0.001 + 0.001) / 2)
    assert idle["client"] == pytest.approx((0.001 + 0.003) / 2)
    assert red["longest_gaps"][0] == ("submit", pytest.approx(0.008))
    bd = tracefile.breakdown(red)
    assert bd["device_ops"][0][0] == "a"
    assert len(bd["idle_gaps"]) <= 10


def test_reduce_needs_the_window_span():
    with pytest.raises(ValueError):
        tracefile.reduce({}, {"submit": [(0.0, 1.0)]})


def _brute_busy(events, w0, w1):
    """Busy nanoseconds by sweeping sorted boundaries (independent of
    ``tracefile._union``)."""
    pts = []
    for _, s, t in events:
        s, t = max(s, w0), min(t, w1)
        if t > s:
            pts += [(s, 1), (t, -1)]
    pts.sort()
    depth, last, busy = 0, None, 0.0
    for x, d in pts:
        if depth > 0:
            busy += x - last
        depth += d
        last = x
    return busy


def test_reduce_the_recorded_chip_trace():
    devices, host = tracefile.load_events(str(CHIP_TRACE))
    assert list(devices) == ["/device:TPU:0"]
    assert len(host["window"]) == 1
    w0, w1 = host["window"][0]
    red = tracefile.reduce(devices, host)
    evs = devices["/device:TPU:0"]
    assert red["busy_s"] == pytest.approx(_brute_busy(evs, w0, w1) * 1e-9)
    assert 0 < red["busy_s"] < red["window_s"]
    # one MLP kernel event per device batch of 2048 rows, and the window's
    # drains retired every one of them
    sec, n = work.kernel_events(red, "mlp")
    assert n > 0 and sec > 0
    assert n == red["op_counts"]["fixedpoint_mlp_pallas"]
    assert work.kernel_events(red, "forest") == (0, 0)
    idle = sum(red["idle_by_activity_s"].values())
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)
    # the client spent most of the window in its own spans
    spans = {k: sum(t - s for s, t in v) * 1e-9 for k, v in host.items()}
    assert spans["submit"] + spans["drain"] > 0.5 * red["window_s"]
    assert np.isfinite(red["window_s"])
