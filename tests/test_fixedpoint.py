"""Unit + property tests for the fixed-point core (paper §3.1, Table 2)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import example, given, settings
from hypothesis import strategies as st

from repro.core import fixedpoint as fx

jax.config.update("jax_enable_x64", False)


class TestEncodeDecode:
    def test_table2_roundtrip_scalar(self):
        # w_q = round(w * 2^s) + b ; w ≈ (w_q - b)/2^s
        w = 0.37
        s, b = 8, 3
        wq = fx.encode(w, s, b)
        assert int(wq) == round(w * 2 ** s) + b
        w_back = fx.decode(wq, s, b)
        assert abs(float(w_back) - w) <= 2 ** (-s - 1) + 1e-9

    def test_saturation(self):
        wq = fx.encode(1e9, 8, total_bits=16)
        assert int(wq) == 2 ** 15 - 1
        wq = fx.encode(-1e9, 8, total_bits=16)
        assert int(wq) == -(2 ** 15)

    def test_round_half_away_from_zero(self):
        assert int(fx.encode(0.5 / 256, 8)) == 1  # 0.5 rounds up
        assert int(fx.encode(-0.5 / 256, 8)) == -1  # -0.5 rounds away

    @given(st.floats(-100.0, 100.0, allow_nan=False),
           st.integers(0, 12), st.integers(-8, 8))
    @settings(max_examples=200, deadline=None)
    @example(w=64.57299497157666, s=11, b=0)
    @example(w=64.18688811832067, s=12, b=0)
    def test_roundtrip_error_bound(self, w, s, b):
        """Property: |decode(encode(w)) − w| ≤ 2^-(s+1) when in range.

        ``encode`` works on ``float32(w)``; scaling it by 2^s and decoding
        are exact, so the bound holds against that value with no slack
        (against the float64 ``w`` the cast adds up to half a float32 ulp,
        3.8e-6 near |w| = 64)."""
        wq = fx.encode(w, s, b, total_bits=32)
        w_back = float(fx.decode(wq, s, b))
        if abs(w * 2 ** s + b) < 2 ** 30:  # not saturated
            assert abs(w_back - float(np.float32(w))) <= 2 ** (-s - 1)

    @given(st.integers(-2**14, 2**14), st.integers(0, 10), st.integers(-4, 4))
    @settings(max_examples=200, deadline=None)
    def test_codes_are_exact_fixed_points(self, q, s, b):
        """Property: values already on the grid encode/decode exactly."""
        w = (q - b) / 2.0 ** s
        assert int(fx.encode(w, s, b)) == q


class TestRoundingShift:
    @given(st.integers(-2**28, 2**28), st.integers(1, 16))
    @settings(max_examples=200, deadline=None)
    def test_matches_float_rounding(self, x, shift):
        got = int(fx._rounding_shift_right(jnp.int32(x), shift))
        want = int(np.floor(x / 2.0 ** shift + 0.5))
        # round-half-up in two's complement == floor(x/2^s + 0.5) for x>=0;
        # for negatives the implementation rounds ties toward zero
        assert abs(got - want) <= 1
        assert abs(got - x / 2.0 ** shift) <= 0.5 + 1e-9

    def test_zero_shift_identity(self):
        assert int(fx._rounding_shift_right(jnp.int32(123), 0)) == 123


class TestQTensorOps:
    def test_qmatmul_matches_float(self):
        rng = np.random.default_rng(0)
        a = rng.normal(size=(8, 16)).astype(np.float32) * 0.5
        w = rng.normal(size=(16, 4)).astype(np.float32) * 0.5
        qa = fx.quantize(a, fx.FixedPointFormat(16, 10))
        qw = fx.quantize(w, fx.FixedPointFormat(16, 10))
        out = fx.qmatmul(qa, qw, out_fmt=fx.INT32)
        got = np.asarray(out.dequantize())
        np.testing.assert_allclose(got, a @ w, atol=0.05)

    def test_qmatmul_rejects_affine(self):
        qa = fx.QTensor(q=jnp.ones((2, 2), jnp.int16), frac_bits=8, offset=1)
        qw = fx.QTensor(q=jnp.ones((2, 2), jnp.int16), frac_bits=8)
        with pytest.raises(ValueError):
            fx.qmatmul(qa, qw)

    def test_qadd_mixed_scales(self):
        a = fx.quantize(np.float32(1.5), fx.FixedPointFormat(16, 8))
        b = fx.quantize(np.float32(0.25), fx.FixedPointFormat(16, 12))
        out = fx.qadd(a, b)
        assert abs(float(out.dequantize()) - 1.75) < 1e-3

    def test_qmul(self):
        a = fx.quantize(np.float32(1.5), fx.FixedPointFormat(16, 8))
        b = fx.quantize(np.float32(-2.0), fx.FixedPointFormat(16, 8))
        out = fx.qmul(a, b)
        assert abs(float(out.dequantize()) + 3.0) < 1e-2

    def test_per_channel_quantize_dequantize(self):
        rng = np.random.default_rng(1)
        w = rng.normal(size=(32, 8)).astype(np.float32)
        w[:, 3] *= 100.0  # outlier channel
        qt = fx.quantize(w, fx.FixedPointFormat(8, 7), channel_axis=1)
        back = np.asarray(qt.dequantize())
        rel = np.abs(back - w).max(0) / (np.abs(w).max(0) + 1e-9)
        assert rel.max() < 0.02  # per-channel scale protects the outlier

    def test_qtensor_is_pytree(self):
        qt = fx.quantize(np.ones((4, 4), np.float32), fx.INT16)
        leaves = jax.tree_util.tree_leaves(qt)
        assert len(leaves) == 1  # channel_scale None
        mapped = jax.tree_util.tree_map(lambda x: x, qt)
        assert isinstance(mapped, fx.QTensor)
        assert mapped.frac_bits == qt.frac_bits


class TestFakeQuant:
    def test_grid_snap(self):
        x = jnp.float32(0.33)
        y = fx.fake_quant(x, 4, 8)
        assert float(y) == round(0.33 * 16) / 16

    def test_ste_gradient(self):
        g = jax.grad(lambda x: fx.fake_quant(x, 4, 8))(jnp.float32(0.3))
        assert float(g) == 1.0
        # out-of-range values get zero gradient (clipped STE)
        g = jax.grad(lambda x: fx.fake_quant(x, 4, 8))(jnp.float32(100.0))
        assert float(g) == 0.0

    @given(st.floats(-4.0, 4.0, allow_nan=False), st.integers(2, 10))
    @settings(max_examples=100, deadline=None)
    def test_idempotent(self, x, frac):
        once = fx.fake_quant(jnp.float32(x), frac, 16)
        twice = fx.fake_quant(once, frac, 16)
        assert float(once) == float(twice)


class TestCalibration:
    def test_calibrate_small_values_gets_more_frac_bits(self):
        small = np.full((100,), 0.01, np.float32)
        big = np.full((100,), 100.0, np.float32)
        assert fx.calibrate_scale(small, 8) > fx.calibrate_scale(big, 8)

    def test_calibrated_format_fits(self):
        rng = np.random.default_rng(2)
        x = rng.normal(size=(1000,)).astype(np.float32) * 3
        fmt = fx.choose_format(x, total_bits=8)
        q = fx.encode(x, fmt.frac_bits, total_bits=8)
        # values must not be badly saturated
        back = np.asarray(fx.decode(q, fmt.frac_bits))
        assert np.abs(back - x).max() < np.abs(x).max() * 0.5
