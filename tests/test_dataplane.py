"""Data-plane tests: packet codec (Table 1), control plane (§2), engine (Fig 2).

The BMv2-software-simulation stage of the paper's methodology maps to these
CPU tests: generate traffic (the Scapy analogue), push it through the jit'd
data plane, verify correctness and packet behaviour.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import packet as pk
from repro.core.control_plane import ControlPlane
from repro.core.inference import DataPlaneEngine


# ---------------------------------------------------------------------------
# Packet codec
# ---------------------------------------------------------------------------


class TestPacketCodec:
    def test_header_layout_bytes(self):
        """Field offsets/widths exactly as published in Table 1."""
        feats = jnp.asarray([[0x01020304, -2]], jnp.int32)
        pkts = pk.encode_packets(model_id=jnp.int32(0xABCD), scale=jnp.int32(8),
                                 features_q=feats, flags=jnp.int32(0x5A))
        row = np.asarray(pkts)[0]
        assert row.shape[0] == pk.packet_nbytes(2) == 7 + 8
        assert row[0] == 0xAB and row[1] == 0xCD            # Model ID u16
        assert row[2] == 2                                   # Feature Cnt u8
        assert row[3] == 0                                   # Output Cnt u8
        assert row[4] == 0 and row[5] == 8                   # Scale u16
        assert row[6] == 0x5A                                # Flags u8
        assert list(row[7:11]) == [1, 2, 3, 4]               # feature 1 BE
        assert list(row[11:15]) == [0xFF, 0xFF, 0xFF, 0xFE]  # −2 two's compl.

    def test_roundtrip(self):
        rng = np.random.default_rng(0)
        feats = rng.integers(-2**31, 2**31 - 1, size=(16, 5), dtype=np.int64)
        feats = jnp.asarray(feats, jnp.int32)
        pkts = pk.encode_packets(jnp.int32(7), jnp.int32(12), feats)
        parsed = pk.parse_packets(pkts, max_features=8)
        assert np.all(np.asarray(parsed.model_id) == 7)
        assert np.all(np.asarray(parsed.scale) == 12)
        assert np.all(np.asarray(parsed.feature_cnt) == 5)
        np.testing.assert_array_equal(np.asarray(parsed.features_q[:, :5]),
                                      np.asarray(feats))
        assert np.all(np.asarray(parsed.features_q[:, 5:]) == 0)

    @given(st.integers(0, 65535), st.integers(0, 255), st.integers(1, 8),
           st.lists(st.integers(-2**31, 2**31 - 1), min_size=8, max_size=8))
    @settings(max_examples=50, deadline=None)
    def test_roundtrip_property(self, mid, flags, nf, vals):
        feats = jnp.asarray([vals[:nf]], jnp.int32)
        pkts = pk.encode_packets(jnp.int32(mid), jnp.int32(9), feats,
                                 flags=jnp.int32(flags))
        parsed = pk.parse_packets(pkts, max_features=nf)
        assert int(parsed.model_id[0]) == mid
        assert int(parsed.flags[0]) == flags
        np.testing.assert_array_equal(np.asarray(parsed.features_q[0]),
                                      np.asarray(vals[:nf], np.int32))

    def test_emit_results_rewrites_header(self):
        feats = jnp.zeros((4, 3), jnp.int32)
        pkts = pk.encode_packets(jnp.int32(5), jnp.int32(8), feats)
        parsed = pk.parse_packets(pkts, max_features=3)
        out = pk.emit_results(parsed, jnp.ones((4, 2), jnp.int32) * 99, out_scale=10)
        reparsed = pk.parse_packets(out, max_features=2)
        assert np.all(np.asarray(reparsed.scale) == 10)
        assert np.all(np.asarray(reparsed.flags) & pk.FLAG_RESULT)
        assert np.all(np.asarray(reparsed.feature_cnt) == 2)
        assert np.all(np.asarray(reparsed.features_q) == 99)

    def test_overhead_matches_fig1_axis(self):
        # Fig 1 x-axis: header bits = 56 + 32·features
        for n in (1, 2, 4, 8, 16):
            assert pk.packet_nbytes(n) * 8 == 56 + 32 * n


# ---------------------------------------------------------------------------
# Control plane
# ---------------------------------------------------------------------------


def _toy_model(rng, dims, scale=0.5):
    layers = []
    for din, dout in zip(dims[:-1], dims[1:]):
        layers.append((rng.normal(size=(din, dout)).astype(np.float32) * scale,
                       rng.normal(size=(dout,)).astype(np.float32) * scale))
    return layers


class TestControlPlane:
    def test_install_and_lookup(self):
        cp = ControlPlane(max_models=4, max_layers=3, max_width=8)
        rng = np.random.default_rng(0)
        slot = cp.install(42, _toy_model(rng, [4, 8, 2]), ["relu"])
        t = cp.tables()
        assert int(t.id_map[42]) == slot
        assert int(t.out_dim[slot]) == 2
        assert np.asarray(t.layer_on[slot]).tolist() == [1, 1, 0]

    def test_hot_swap_same_slot(self):
        cp = ControlPlane(max_models=2, max_layers=2, max_width=4)
        rng = np.random.default_rng(1)
        s1 = cp.install(1, _toy_model(rng, [2, 2]), [])
        v1 = cp.version
        s2 = cp.install(1, _toy_model(rng, [2, 2]), [])
        assert s1 == s2 and cp.version == v1 + 1

    def test_capacity_enforced(self):
        cp = ControlPlane(max_models=1, max_layers=1, max_width=4)
        rng = np.random.default_rng(2)
        cp.install(0, _toy_model(rng, [2, 2]), [])
        with pytest.raises(ValueError):
            cp.install(9, _toy_model(rng, [2, 2]), [])

    def test_remove(self):
        cp = ControlPlane(max_models=2, max_layers=1, max_width=4)
        rng = np.random.default_rng(3)
        cp.install(5, _toy_model(rng, [2, 2]), [])
        cp.remove(5)
        assert int(cp.tables().id_map[5]) == -1


# ---------------------------------------------------------------------------
# End-to-end engine (Fig 2 pipeline)
# ---------------------------------------------------------------------------


def _float_forward(layers, acts, x, final="none"):
    names = list(acts) + [final]
    for (w, b), act in zip(layers, names):
        x = x @ w + b
        if act == "relu":
            x = np.maximum(x, 0)
        elif act == "sigmoid":
            x = 1 / (1 + np.exp(-x))
    return x


class TestDataPlaneEngine:
    def _setup(self, frac=10, order=3, width=16):
        cp = ControlPlane(max_models=4, max_layers=3, max_width=width,
                          weight_bits=16, frac_bits=frac)
        eng = DataPlaneEngine(cp, max_features=width, taylor_order=order)
        return cp, eng

    def test_linear_regression_exact(self):
        """A pure-linear model through the integer pipeline matches floats to
        grid resolution."""
        cp, eng = self._setup()
        rng = np.random.default_rng(0)
        layers = _toy_model(rng, [4, 2], scale=0.3)
        cp.install(1, layers, [])
        x = rng.normal(size=(32, 4)).astype(np.float32) * 0.5
        xq = np.round(x * 2 ** cp.frac_bits).astype(np.int32)
        pkts = pk.encode_packets(jnp.int32(1), jnp.int32(cp.frac_bits),
                                 jnp.asarray(xq))
        out = eng.process(pkts)
        parsed = pk.parse_packets(out, max_features=2)
        got = np.asarray(parsed.features_q[:, :2]) / 2.0 ** cp.frac_bits
        want = _float_forward(layers, [], x)
        np.testing.assert_allclose(got, want, atol=0.02)

    def test_mlp_with_taylor_sigmoid(self):
        """2-layer MLP with sigmoid hidden activation ≈ float reference —
        the paper's end-to-end accuracy check (NMSE well under Fig-3's 0.15)."""
        cp, eng = self._setup(frac=10, order=5)
        rng = np.random.default_rng(1)
        layers = _toy_model(rng, [4, 8, 2], scale=0.4)
        cp.install(3, layers, ["sigmoid"])
        x = rng.normal(size=(64, 4)).astype(np.float32) * 0.5
        xq = np.round(x * 2 ** cp.frac_bits).astype(np.int32)
        pkts = pk.encode_packets(jnp.int32(3), jnp.int32(cp.frac_bits),
                                 jnp.asarray(xq))
        out = eng.process(pkts)
        parsed = pk.parse_packets(out, max_features=2)
        got = np.asarray(parsed.features_q[:, :2]) / 2.0 ** cp.frac_bits
        want = _float_forward(layers, ["sigmoid"], x)
        nmse = ((got - want) ** 2).mean() / (want ** 2).mean()
        assert nmse < 0.02

    def test_weight_update_does_not_recompile(self):
        """THE control-plane property: hot-swapping weights must not
        re-trace/re-compile the data plane (FPGA re-synthesis analogue)."""
        cp, eng = self._setup()
        rng = np.random.default_rng(2)
        cp.install(1, _toy_model(rng, [4, 2]), [])
        pkts = pk.encode_packets(jnp.int32(1), jnp.int32(cp.frac_bits),
                                 jnp.zeros((8, 4), jnp.int32))
        eng.process(pkts)
        assert eng.trace_count == 1
        for _ in range(5):
            cp.install(1, _toy_model(rng, [4, 2]), [])  # retrain + hot swap
            eng.process(pkts)
        assert eng.trace_count == 1  # no re-synthesis

    def test_multi_model_dispatch(self):
        """Packets with different Model IDs hit their own tables in one batch."""
        cp, eng = self._setup()
        w_a = [(np.eye(2, dtype=np.float32) * 2.0, np.zeros(2, np.float32))]
        w_b = [(np.eye(2, dtype=np.float32) * -1.0, np.zeros(2, np.float32))]
        cp.install(10, w_a, [])
        cp.install(20, w_b, [])
        x = np.asarray([[1.0, 0.5]] * 4, np.float32)
        xq = jnp.asarray(np.round(x * 2 ** cp.frac_bits).astype(np.int32))
        mids = jnp.asarray([10, 20, 10, 20], jnp.int32)
        pkts = pk.encode_packets(mids, jnp.int32(cp.frac_bits), xq)
        parsed = pk.parse_packets(eng.process(pkts), max_features=2)
        got = np.asarray(parsed.features_q[:, :2]) / 2.0 ** cp.frac_bits
        np.testing.assert_allclose(got[0], [2.0, 1.0], atol=0.01)
        np.testing.assert_allclose(got[1], [-1.0, -0.5], atol=0.01)

    def test_unknown_model_id_zeroed(self):
        cp, eng = self._setup()
        rng = np.random.default_rng(4)
        cp.install(1, _toy_model(rng, [2, 2]), [])
        pkts = pk.encode_packets(jnp.int32(999), jnp.int32(cp.frac_bits),
                                 jnp.ones((2, 2), jnp.int32) * 100)
        parsed = pk.parse_packets(eng.process(pkts), max_features=2)
        assert np.all(np.asarray(parsed.features_q) == 0)

    def test_relu_and_leaky_paths(self):
        cp, eng = self._setup()
        w = [(np.eye(2, dtype=np.float32), np.zeros(2, np.float32)),
             (np.eye(2, dtype=np.float32), np.zeros(2, np.float32))]
        cp.install(1, w, ["relu"])
        x = np.asarray([[-1.0, 2.0]], np.float32)
        xq = jnp.asarray(np.round(x * 2 ** cp.frac_bits).astype(np.int32))
        pkts = pk.encode_packets(jnp.int32(1), jnp.int32(cp.frac_bits), xq)
        parsed = pk.parse_packets(eng.process(pkts), max_features=2)
        got = np.asarray(parsed.features_q[:, :2]) / 2.0 ** cp.frac_bits
        np.testing.assert_allclose(got, [[0.0, 2.0]], atol=0.01)

    def test_batch_throughput_counters(self):
        cp, eng = self._setup()
        rng = np.random.default_rng(5)
        cp.install(1, _toy_model(rng, [4, 2]), [])
        pkts = pk.encode_packets(jnp.int32(1), jnp.int32(cp.frac_bits),
                                 jnp.zeros((256, 4), jnp.int32))
        out = eng.process(pkts)
        assert eng.stats["packets"] == 256
        assert eng.stats["bytes_in"] == pkts.size
        assert eng.stats["bytes_out"] == out.size
