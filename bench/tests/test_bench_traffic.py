"""The benchmark's copied traffic generators (CPU, tiny sizes)."""

from __future__ import annotations

from pathlib import Path

import numpy as np
import pytest

from benchlib import gen
from benchlib.reference import Reference
from benchlib.wire import encode_wire

BENCH = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("kw", [
    dict(pattern="bursty", fixed_length=False),
    dict(pattern="mixed"),
    dict(pattern="periodic", jitter=5, base_period=512),
])
def test_raw_trace_copy_gives_the_program_generators_bytes(kw):
    from repro.data.packets import raw_trace
    ours, flow = gen.raw_trace(np.random.default_rng(11), 5000, n_flows=97,
                               model_ids=[1, 2, 101], **kw)
    theirs = raw_trace(np.random.default_rng(11), 5000, n_flows=97,
                       model_ids=[1, 2, 101], **kw)
    assert np.array_equal(ours, theirs)
    assert flow.shape == (5000,) and flow.max() < 97


def test_wire_encoder_gives_the_program_encoders_bytes():
    from repro.core.packet import encode_packets_np
    rng = np.random.default_rng(3)
    mid = rng.integers(0, 1 << 16, 64)
    x = rng.integers(-(1 << 31), 1 << 31, (64, 32)).astype(np.int32)
    assert np.array_equal(encode_wire(mid, 8, x, flags=2, output_cnt=32),
                          encode_packets_np(mid, 8, x, flags=2,
                                            output_cnt=32))


def test_generators_repeat_from_the_same_seed():
    seed = 2 ** 31 + 5
    a = gen.raw_trace(gen.stream_rng(seed, 2), 3000, n_flows=50,
                      model_ids=[1, 2], pattern="bursty")
    b = gen.raw_trace(gen.stream_rng(seed, 2), 3000, n_flows=50,
                      model_ids=[1, 2], pattern="bursty")
    c = gen.raw_trace(gen.stream_rng(seed + 1, 2), 3000, n_flows=50,
                      model_ids=[1, 2], pattern="bursty")
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    p1 = gen.wire_pool(gen.stream_rng(seed, 2), n_rows=256,
                       model_ids=[1, 2], width=32, lo=-9, hi=9, frac=8)
    p2 = gen.wire_pool(gen.stream_rng(seed, 2), n_rows=256,
                       model_ids=[1, 2], width=32, lo=-9, hi=9, frac=8)
    assert np.array_equal(p1, p2)


@pytest.mark.parametrize("mix", ["raw-cold", "unique", "raw-cold-rate"])
def test_each_mix_builds_the_same_traffic_from_the_same_seed(
        small_root, mix):
    from benchlib import harness
    bench = harness.load_benchmark(small_root)
    cell = next(w for w in bench["workloads"] if w["traffic"] == mix)
    cfg = harness.load_config(small_root, bench, cell["config"])
    m = harness.load_mix(small_root, mix)
    build = harness.load_plugin(small_root, "generators",
                                m["generator"]).build
    one, two = (build(m, cfg, 2 ** 32 + 3, 0.05) for _ in range(2))
    other = build(m, cfg, 2 ** 32 + 4, 0.05)
    for f in ("rows", "setup_raw", "setup_wire", "sample"):
        assert np.array_equal(getattr(one, f), getattr(two, f)), f
    assert not np.array_equal(one.rows, other.rows)
    assert one.sample.size and (np.diff(one.sample) > 0).all()
    assert one.rows.shape[0] >= m["loop"]["max_burst"]


def _cfg(lanes_width=32):
    return {"server": {"frac_bits": 8, "max_width": lanes_width,
                       "max_tree_depth": 6, "taylor_order": 3},
            "semantics": {"leaky_alpha": 0.01,
                          "sigmoid_series": [0.5, 0.25, 0.0, -1 / 48],
                          "flow": {"ewma_shift": 3, "byte_shift": 6,
                                   "dur_shift": 10, "cms_depth": 2,
                                   "cms_width_pow2": 12}}}


def _rows(ref, stream, mids):
    pos = np.arange(stream.shape[0])
    feats = ref.flow_features(stream, pos)
    return encode_wire(mids, 8, ref.gather(feats, mids))


def test_cold_rows_are_all_distinct():
    lanes = list(range(8))
    rows, flow = gen.raw_trace(np.random.default_rng(21), 2000, n_flows=64,
                               model_ids=[1, 2], pattern="bursty",
                               fixed_length=False)
    ref = Reference(_cfg(), [
        {"id": 1, "kind": "mlp", "spec": tuple(lanes * 4)},
        {"id": 2, "kind": "forest", "spec": tuple(lanes)}])
    mids = (rows[:, 13].astype(np.int32) << 8) | rows[:, 14]
    wire = _rows(ref, rows, mids)
    assert np.unique(wire, axis=0).shape[0] == wire.shape[0]


def test_cyclic_take_wraps_the_pool():
    t = gen.Traffic("wire", np.arange(10, dtype=np.uint8).reshape(10, 1),
                    True, np.zeros((0, 21), np.uint8),
                    np.zeros((0, 1), np.uint8), np.zeros(0, np.int64))
    assert t.take(8, 4).ravel().tolist() == [8, 9, 0, 1]
    assert t.row_at(np.asarray([13])).ravel().tolist() == [3]


def test_finite_traffic_that_runs_out_fails():
    t = gen.Traffic("raw", np.zeros((10, 21), np.uint8), False,
                    np.zeros((0, 21), np.uint8), np.zeros((0, 1), np.uint8),
                    np.zeros(0, np.int64))
    with pytest.raises(RuntimeError, match="ran out"):
        t.take(8, 4)


def test_fixed_rate_due_times_come_from_the_seed():
    from benchlib import harness
    poisson = harness.load_plugin(BENCH.parent, "loops", "poisson")
    one, two = (poisson.due_times(2 ** 32 + 5, 1000.0, 2.0) for _ in "ab")
    other = poisson.due_times(2 ** 32 + 6, 1000.0, 2.0)
    assert np.array_equal(one, two) and not np.array_equal(one, other)
    assert (np.diff(one) > 0).all() and one[-1] < 2.0
    assert abs(one.size - 2000) < 200
