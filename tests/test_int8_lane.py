"""int8 weight-lane variant of the fused multi-model MLP kernel.

The lane contract (``ref.fused_mlp_ref(..., lane_bits=8)``): weight codes are
int8 (control plane at ``weight_bits=8``), feature codes saturate into the
int8 lane at entry and after every layer's requantize+activation, and the
layer dot is an int8×int8→int32 contraction.  Every backend — the Pallas
kernel (interpret mode off-TPU), the masked-GEMM oracle, and the CPU gather
lowering — must agree bit for bit, and the engine must reject configurations
where the narrowing cast could silently truncate installed models.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import packet as pk
from repro.core.control_plane import ControlPlane
from repro.core.inference import DataPlaneEngine
from repro.core.taylor import scaled_constants
from repro.kernels import KERNEL_VARIANTS
from repro.kernels import ref
from repro.kernels.ops import fused_mlp
from repro.kernels.ref import lane_clamp

FRAC = 5  # int8 lane: codes in [-128, 127] → |x| < 4.0 at 5 fractional bits


def _zoo(cp, rng, n_models, width, scale=0.3):
    acts = ["relu", "sigmoid", "leaky_relu", "hard_sigmoid", "none"]
    for m in range(n_models):
        depth = 1 + m % cp.max_layers
        dims = [width] * depth + [1 + m % width]
        layers = [(rng.normal(size=(a, b)).astype(np.float32) * scale,
                   rng.normal(size=(b,)).astype(np.float32) * scale)
                  for a, b in zip(dims[:-1], dims[1:])]
        hidden = [acts[(m + i) % len(acts)] for i in range(depth - 1)]
        cp.install(100 + m, layers, hidden,
                   final_activation=acts[m % len(acts)])


class TestInt8Lane:
    def test_variant_registry(self):
        assert KERNEL_VARIANTS == ("int16", "int8")

    @pytest.mark.parametrize("width,n_models,batch",
                             [(8, 4, 64), (16, 8, 300)])
    def test_backends_bit_exact(self, width, n_models, batch):
        """pallas(interpret, int8) == int8 oracle == CPU gather lowering,
        bit for bit, across every activation opcode and padded depth."""
        rng = np.random.default_rng(width * n_models)
        cp = ControlPlane(max_models=n_models, max_layers=3, max_width=width,
                          weight_bits=8, frac_bits=FRAC)
        _zoo(cp, rng, n_models, width)
        t = cp.tables()
        # codes beyond the int8 lane on purpose: entry saturation is part of
        # the contract and must agree across backends
        x = jnp.asarray(rng.integers(-1000, 1000, (batch, width)), jnp.int32)
        slot = jnp.asarray(rng.integers(0, n_models, batch), jnp.int32)
        kw = dict(frac=FRAC, sig_coeffs=scaled_constants("sigmoid", 3, FRAC),
                  leaky_alpha_q=2, variant="int8")
        outs = {b: np.asarray(fused_mlp(x, slot, t.w, t.b, t.act, t.layer_on,
                                        backend=b, **kw))
                for b in ("ref", "pallas", "auto")}
        np.testing.assert_array_equal(outs["pallas"], outs["ref"])
        np.testing.assert_array_equal(outs["auto"], outs["ref"])
        # every output already sits inside the int8 lane
        assert np.asarray(lane_clamp(jnp.asarray(outs["ref"]), 8)).tolist() \
            == outs["ref"].tolist()

    def test_int8_differs_from_int16_when_saturating(self):
        """The lane is a real semantic: inputs that overflow int8 must take
        the saturated path, not silently match the 16-bit lane."""
        rng = np.random.default_rng(3)
        cp = ControlPlane(max_models=2, max_layers=2, max_width=8,
                          weight_bits=8, frac_bits=FRAC)
        _zoo(cp, rng, 2, 8)
        t = cp.tables()
        x = jnp.asarray(rng.integers(200, 2000, (32, 8)), jnp.int32)
        slot = jnp.zeros(32, jnp.int32)
        kw = dict(frac=FRAC, sig_coeffs=scaled_constants("sigmoid", 3, FRAC),
                  leaky_alpha_q=2)
        a = np.asarray(fused_mlp(x, slot, t.w, t.b, t.act, t.layer_on,
                                 backend="ref", variant="int8", **kw))
        b = np.asarray(fused_mlp(x, slot, t.w, t.b, t.act, t.layer_on,
                                 backend="ref", variant="int16", **kw))
        assert not np.array_equal(a, b)

    def test_engine_fused_matches_gather_and_float(self):
        rng = np.random.default_rng(5)
        width = 8
        cp = ControlPlane(max_models=4, max_layers=2, max_width=width,
                          weight_bits=8, frac_bits=FRAC)
        models = {}
        for m in range(4):
            w = rng.normal(size=(width, 2)).astype(np.float32) * 0.4
            bias = rng.normal(size=(2,)).astype(np.float32) * 0.2
            cp.install(50 + m, [(w, bias)], [])
            models[50 + m] = (w, bias)
        eng = DataPlaneEngine(cp, max_features=width, kernel_variant="int8")
        b = 128
        mids = rng.integers(50, 54, b).astype(np.int32)
        x = (rng.normal(size=(b, width)) * 0.5).astype(np.float32)
        xq = np.round(x * 2.0 ** FRAC).astype(np.int32)
        pkts = pk.encode_packets(jnp.asarray(mids), jnp.int32(FRAC),
                                 jnp.asarray(xq))
        egress = eng.process(pkts)
        # the int8 lane of the per-packet weight gather, applied directly
        parsed_in = pk.parse_packets(pkts, width)
        t = cp.tables()
        slot = t.id_map[parsed_in.model_id]
        lane_out = ref.fused_mlp_gather_ref(
            parsed_in.features_q, slot, t.w, t.b, t.act, t.layer_on,
            frac=FRAC, sig_coeffs=eng.lane_cfg.sig_coeffs,
            leaky_alpha_q=eng.lane_cfg.leaky_alpha_q, lane_bits=8)
        lane_out = jnp.where(
            jnp.arange(width)[None, :] < t.out_dim[slot][:, None],
            lane_out, 0)
        np.testing.assert_array_equal(
            np.asarray(egress),
            np.asarray(pk.emit_results(parsed_in, lane_out, FRAC)))
        parsed = pk.parse_packets(egress, max_features=2)
        got = np.asarray(parsed.features_q[:, :2]) / 2.0 ** FRAC
        want = np.stack([x[i] @ models[int(mids[i])][0]
                         + models[int(mids[i])][1] for i in range(b)])
        # coarse grid (5 frac bits) + int8 weights → loose but real bound
        np.testing.assert_allclose(got, want, atol=0.15)

    def test_zero_retraces_across_installs(self):
        rng = np.random.default_rng(6)
        cp = ControlPlane(max_models=4, max_layers=2, max_width=8,
                          weight_bits=8, frac_bits=FRAC)
        _zoo(cp, rng, 4, 8)
        eng = DataPlaneEngine(cp, max_features=8, kernel_variant="int8")
        pkts = pk.encode_packets(jnp.int32(100), jnp.int32(FRAC),
                                 jnp.zeros((16, 8), jnp.int32))
        eng.process(pkts)
        _zoo(cp, rng, 4, 8, scale=0.5)
        eng.process(pkts)
        assert eng.trace_count == 1

    def test_wide_weight_format_rejected(self):
        cp = ControlPlane(max_models=2, max_layers=1, max_width=4,
                          weight_bits=16, frac_bits=8)
        with pytest.raises(ValueError, match="weight_bits"):
            DataPlaneEngine(cp, kernel_variant="int8")

    def test_unknown_variant_rejected(self):
        cp = ControlPlane(max_models=2, max_layers=1, max_width=4)
        with pytest.raises(ValueError, match="variant"):
            DataPlaneEngine(cp, kernel_variant="int4")
        with pytest.raises(ValueError, match="variant"):
            fused_mlp(jnp.zeros((4, 4), jnp.int32), jnp.zeros(4, jnp.int32),
                      jnp.zeros((2, 1, 4, 4), jnp.int32),
                      jnp.zeros((2, 1, 4), jnp.int32),
                      jnp.zeros((2, 1), jnp.int32),
                      jnp.zeros((2, 1), jnp.int32),
                      frac=8, sig_coeffs=(0, 1), leaky_alpha_q=1,
                      variant="int4")
