"""Tentpole tests: batched multi-model dispatch, the fused Pallas MLP kernel,
double-buffered table installs, and the async serving loop.

The fused kernel must be bit-exact with (a) its jnp oracle, (b) the fast CPU
lowering, and (c) the per-packet weight gather of ``kernels.ref`` — the data
plane's integer semantics are the contract (P4/FPGA bit-equivalence,
DESIGN.md §2).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import packet as pk
from repro.core.control_plane import ControlPlane
from repro.core.inference import DataPlaneEngine
from repro.core.ingress import PacketError
from repro.core.taylor import scaled_constants
from repro.kernels import ref
from repro.kernels.ops import fused_mlp

FRAC = 8


def _install_zoo(cp, rng, n_models, width, scale=0.3):
    """Install ``n_models`` MLPs exercising every activation opcode and
    several depths/widths (padded tables must mask correctly)."""
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


class TestFusedKernel:
    @pytest.mark.parametrize("width,n_models,batch", [(8, 4, 64), (16, 16, 300)])
    def test_backends_bit_exact(self, width, n_models, batch):
        """pallas(interpret) == masked-GEMM oracle == CPU gather lowering ==
        the seed per-packet-gather engine loop, bit for bit."""
        rng = np.random.default_rng(width + n_models)
        cp = ControlPlane(max_models=n_models, max_layers=3, max_width=width,
                          frac_bits=FRAC)
        _install_zoo(cp, rng, n_models, width)
        t = cp.tables()
        x = jnp.asarray(rng.integers(-2000, 2000, (batch, width)), jnp.int32)
        slot = jnp.asarray(rng.integers(0, n_models, batch), jnp.int32)
        coeffs = scaled_constants("sigmoid", 3, FRAC)
        kw = dict(frac=FRAC, sig_coeffs=coeffs, leaky_alpha_q=3)

        outs = {b: np.asarray(fused_mlp(x, slot, t.w, t.b, t.act, t.layer_on,
                                        backend=b, **kw))
                for b in ("ref", "pallas", "auto")}
        # the per-packet weight gather, straight from kernels.ref, the
        # one place the integer semantics live
        gathered = np.asarray(jax.jit(
            lambda x, s: ref.fused_mlp_gather_ref(
                x, s, t.w, t.b, t.act, t.layer_on, **kw))(x, slot))

        np.testing.assert_array_equal(outs["pallas"], outs["ref"])
        np.testing.assert_array_equal(outs["auto"], outs["ref"])
        np.testing.assert_array_equal(gathered, outs["ref"])

    def test_pallas_exact_over_full_int32_range(self):
        """Codes over the whole int32 range against weight codes at the
        int16 rails: the kernel's digit-plane dots must reproduce the
        oracle's wrapping int32 dot bit for bit."""
        rng = np.random.default_rng(5)
        width, n_models, batch = 8, 4, 64
        cp = ControlPlane(max_models=n_models, max_layers=2,
                          max_width=width, frac_bits=FRAC)
        _install_zoo(cp, rng, n_models, width, scale=200.0)
        t = cp.tables()
        assert np.abs(np.asarray(t.w)).max() == 2 ** 15 - 1  # rails hit
        x = jnp.asarray(rng.integers(-2 ** 31, 2 ** 31, (batch, width),
                                     dtype=np.int64).astype(np.int32))
        slot = jnp.asarray(rng.integers(0, n_models, batch), jnp.int32)
        kw = dict(frac=FRAC, sig_coeffs=scaled_constants("sigmoid", 3, FRAC),
                  leaky_alpha_q=3)
        a, b = (np.asarray(fused_mlp(x, slot, t.w, t.b, t.act, t.layer_on,
                                     backend=bk, **kw))
                for bk in ("pallas", "ref"))
        np.testing.assert_array_equal(a, b)

    def test_pallas_padding_path(self):
        """Batch sizes that are not tile multiples round-trip unharmed."""
        rng = np.random.default_rng(0)
        cp = ControlPlane(max_models=2, max_layers=2, max_width=4,
                          frac_bits=FRAC)
        _install_zoo(cp, rng, 2, 4)
        t = cp.tables()
        coeffs = scaled_constants("sigmoid", 3, FRAC)
        kw = dict(frac=FRAC, sig_coeffs=coeffs, leaky_alpha_q=3)
        for batch in (1, 7, 257):
            x = jnp.asarray(rng.integers(-500, 500, (batch, 4)), jnp.int32)
            slot = jnp.asarray(rng.integers(0, 2, batch), jnp.int32)
            a = np.asarray(fused_mlp(x, slot, t.w, t.b, t.act, t.layer_on,
                                     backend="pallas", **kw))
            b = np.asarray(fused_mlp(x, slot, t.w, t.b, t.act, t.layer_on,
                                     backend="ref", **kw))
            np.testing.assert_array_equal(a, b)


def _gather_ref_egress(eng, pkts):
    """The wire path's egress with the MLP lane computed by
    ``ref.fused_mlp_gather_ref`` on the control plane's tables: each
    packet's own weights gathered, out_dim lanes kept, unknown Model IDs
    zeroed."""
    t = eng.cp.tables()
    parsed = pk.parse_packets(jnp.asarray(pkts), eng.max_features)
    slot = t.id_map[parsed.model_id]
    live = jnp.maximum(slot, 0)
    x = ref.fused_mlp_gather_ref(
        parsed.features_q, live, t.w, t.b, t.act, t.layer_on,
        frac=eng.frac, sig_coeffs=eng.lane_cfg.sig_coeffs,
        leaky_alpha_q=eng.lane_cfg.leaky_alpha_q)
    keep = ((jnp.arange(x.shape[1])[None, :] < t.out_dim[live][:, None])
            & (slot >= 0)[:, None])
    return np.asarray(pk.emit_results(parsed, jnp.where(keep, x, 0),
                                      eng.frac))


class TestBatchedEngine:
    def _engine(self, n_models=8, width=8):
        rng = np.random.default_rng(42)
        cp = ControlPlane(max_models=n_models, max_layers=3, max_width=width,
                          frac_bits=FRAC)
        _install_zoo(cp, rng, n_models, width)
        return cp, DataPlaneEngine(cp, max_features=width)

    def test_fused_matches_gather_engine(self):
        """Whole-pipeline equality with the per-packet weight gather on an
        arbitrarily interleaved batch, including unknown Model IDs (zeroed
        egress)."""
        rng = np.random.default_rng(3)
        cp, eng = self._engine()
        b = 200
        mids = rng.integers(100, 110, b).astype(np.int32)  # 108/109 unknown
        codes = rng.integers(-2000, 2000, (b, 8)).astype(np.int32)
        pkts = pk.encode_packets(jnp.asarray(mids), jnp.int32(FRAC),
                                 jnp.asarray(codes))
        want = _gather_ref_egress(eng, pkts)
        assert want[mids < 108, pk.HEADER_BYTES:].any()  # real outputs
        np.testing.assert_array_equal(np.asarray(eng.process(pkts)), want)

    def test_mixed_batch_matches_float_reference(self):
        """Each packet's output ≈ its own model's float forward pass."""
        rng = np.random.default_rng(5)
        width = 8
        cp = ControlPlane(max_models=4, max_layers=2, max_width=width,
                          frac_bits=10)
        models = {}
        for m in range(4):
            w = rng.normal(size=(width, 2)).astype(np.float32) * 0.4
            bias = rng.normal(size=(2,)).astype(np.float32) * 0.2
            cp.install(50 + m, [(w, bias)], [])
            models[50 + m] = (w, bias)
        eng = DataPlaneEngine(cp, max_features=width)
        b = 128
        mids = rng.integers(50, 54, b).astype(np.int32)
        x = (rng.normal(size=(b, width)) * 0.5).astype(np.float32)
        xq = np.round(x * 2.0 ** 10).astype(np.int32)
        pkts = pk.encode_packets(jnp.asarray(mids), jnp.int32(10),
                                 jnp.asarray(xq))
        parsed = pk.parse_packets(eng.process(pkts), max_features=2)
        got = np.asarray(parsed.features_q[:, :2]) / 2.0 ** 10
        want = np.stack([x[i] @ models[int(mids[i])][0]
                         + models[int(mids[i])][1] for i in range(b)])
        np.testing.assert_allclose(got, want, atol=0.02)

    def test_zero_retraces_across_installs(self):
        rng = np.random.default_rng(6)
        cp, eng = self._engine()
        pkts = pk.encode_packets(jnp.int32(100), jnp.int32(FRAC),
                                 jnp.zeros((16, 8), jnp.int32))
        eng.process(pkts)
        assert eng.trace_count == 1
        for _ in range(4):
            _install_zoo(cp, rng, 8, 8)  # hot-swap every model
            eng.process(pkts)
        assert eng.trace_count == 1  # no data-plane re-synthesis


class TestDoubleBufferedInstall:
    def test_inflight_generation_isolated(self):
        """A snapshot taken before install() keeps serving the old weights —
        the writer swaps a generation, never mutates published buffers."""
        cp = ControlPlane(max_models=2, max_layers=1, max_width=2,
                          frac_bits=FRAC)
        w_old = np.eye(2, dtype=np.float32)
        w_new = np.eye(2, dtype=np.float32) * 3.0
        cp.install(7, [(w_old, np.zeros(2, np.float32))], [])
        before = cp.tables()  # "in-flight" batch's generation
        gen0 = cp.version
        cp.install(7, [(w_new, np.zeros(2, np.float32))], [])
        after = cp.tables()
        assert cp.version == gen0 + 1
        # old snapshot untouched; new snapshot carries the retrained weights
        one = int(round(2.0 ** FRAC))
        assert int(before.w[0, 0, 0, 0]) == one
        assert int(after.w[0, 0, 0, 0]) == 3 * one

    def test_snapshot_cached_per_generation(self):
        """Steady-state serving re-feeds the same device buffers (no
        per-batch host→device upload); a write publishes fresh ones."""
        cp = ControlPlane(max_models=1, max_layers=1, max_width=2)
        cp.install(1, [(np.eye(2, dtype=np.float32), np.zeros(2, np.float32))], [])
        t1, t2 = cp.tables(), cp.tables()
        assert t1 is t2
        cp.install(1, [(np.eye(2, dtype=np.float32), np.zeros(2, np.float32))], [])
        assert cp.tables() is not t1

    def test_remove_is_copy_on_write(self):
        cp = ControlPlane(max_models=2, max_layers=1, max_width=2)
        cp.install(1, [(np.eye(2, dtype=np.float32), np.zeros(2, np.float32))], [])
        before = cp.tables()
        cp.remove(1)
        assert int(before.id_map[1]) >= 0      # old generation still routes
        assert int(cp.tables().id_map[1]) == -1

    def test_remove_recycles_slot_without_collision(self):
        """A slot freed by remove() must never be handed to a new model while
        still routing a live one."""
        eye = [(np.eye(2, dtype=np.float32), np.zeros(2, np.float32))]
        two = [(np.eye(2, dtype=np.float32) * 2, np.zeros(2, np.float32))]
        cp = ControlPlane(max_models=2, max_layers=1, max_width=2)
        s1 = cp.install(1, eye, [])
        s2 = cp.install(2, two, [])
        cp.remove(1)
        s3 = cp.install(3, eye, [])
        assert s3 == s1 and s3 != s2  # recycled, not colliding with model 2
        t = cp.tables()
        one = 1 << cp.frac_bits
        assert int(t.w[s2, 0, 0, 0]) == 2 * one  # model 2's weights intact
        with pytest.raises(ValueError):  # both slots live again → table full
            cp.install(4, eye, [])

    def test_failed_install_leaves_no_trace(self):
        """install() is transactional: a rejected model must not consume a
        slot, register an ID, or leave partial tables behind."""
        cp = ControlPlane(max_models=2, max_layers=2, max_width=2)
        good = (np.eye(2, dtype=np.float32), np.zeros(2, np.float32))
        wide = (np.ones((2, 5), np.float32), np.zeros(5, np.float32))
        gen = cp.version
        with pytest.raises(ValueError):
            cp.install(9, [good, wide], ["relu"])
        with pytest.raises(KeyError):
            cp.install(9, [good], ["not_an_activation"])
        assert cp.version == gen
        assert int(cp.tables().id_map[9]) == -1
        s = cp.install(9, [good], [])  # the fixed model installs cleanly
        assert int(cp.tables().layer_on[s, 0]) == 1


class TestAsyncServing:
    """The stream surface serves asynchronously: ``submit_packets`` stages
    and dispatches without waiting for the device, ``drain_packets``
    retires in submission order."""

    def _server(self, **kw):
        from repro.launch.serve import PacketServer
        rng = np.random.default_rng(9)
        srv = PacketServer(max_models=8, max_layers=2, max_width=8,
                           frac_bits=FRAC, **kw)
        _install_zoo(srv.control_plane, rng, 8, 8)
        return srv

    @staticmethod
    def _wire(rng, n, mid=None):
        mids = (np.full(n, mid, np.int32) if mid is not None
                else rng.integers(100, 108, n).astype(np.int32))
        codes = rng.integers(-1000, 1000, (n, 8)).astype(np.int32)
        return np.asarray(pk.encode_packets(jnp.asarray(mids),
                                            jnp.int32(FRAC),
                                            jnp.asarray(codes)))

    @pytest.mark.parametrize("rejects", [False, True])
    def test_inflight_bounded_and_stats(self, rejects):
        """The device window never holds more than ``max_inflight``
        batches, a malformed chunk takes no room in it and resolves as
        per-packet error slots at its own positions, and the counters see
        every served packet once."""
        rng = np.random.default_rng(10)
        srv = self._server(max_inflight=2, ingress_batch=32)
        good, bad = [], []
        n = 0
        for i in range(10 if rejects else 5):
            if rejects and i % 2:
                srv.submit_packets(np.zeros((2, 1), np.uint8))
                bad += [n, n + 1]
                n += 2
            else:
                chunk = self._wire(rng, 32)
                srv.submit_packets(chunk)
                good.append(chunk)
                n += 32
            assert len(srv.ingress._inflight) <= 2
        out = srv.drain_packets()
        assert not srv.ingress._inflight
        assert len(out) == n
        assert [i for i, r in enumerate(out)
                if isinstance(r, PacketError)] == bad
        assert srv.engine.stats["packets"] == 32 * len(good)
        assert srv.stats()["recompiles"] == 1
        want = np.asarray(srv.process(np.concatenate(good)))
        np.testing.assert_array_equal(
            np.stack([r for i, r in enumerate(out) if i not in bad]),
            want[:, : srv.ingress.out_bytes])

    def test_install_mid_flight_zero_retraces(self):
        """The acceptance property end-to-end: hot-swapping every model
        while a batch is in flight never recompiles, the in-flight batch
        keeps its generation and the next packets see the new one."""
        rng = np.random.default_rng(13)
        srv = self._server(ingress_batch=16)
        pkts = self._wire(rng, 16, mid=100)
        want_old = np.asarray(srv.process(pkts))[:, : srv.ingress.out_bytes]
        srv.submit_packets(pkts)          # a full batch: dispatched now
        traces = srv.engine.trace_count
        gen = srv.control_plane.version
        _install_zoo(srv.control_plane, rng, 8, 8, scale=0.5)
        srv.submit_packets(pkts)
        got = srv.drain_packets()
        want_new = np.asarray(srv.process(pkts))[:, : srv.ingress.out_bytes]
        assert srv.engine.trace_count == traces
        assert srv.control_plane.version > gen
        np.testing.assert_array_equal(np.stack(got[:16]), want_old)
        np.testing.assert_array_equal(np.stack(got[16:]), want_new)
        assert not np.array_equal(want_old, want_new)


class _CopySpy:
    """A device result that counts the host-copy starts made on it."""

    def __init__(self, out, starts):
        self._out = out
        self._starts = starts

    def copy_to_host_async(self):
        self._starts.append(1)
        self._out.copy_to_host_async()

    def block_until_ready(self):
        self._out.block_until_ready()
        return self

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self._out, dtype)


class TestHostCopyAtDispatch:
    """``run_features(block=False)`` starts the result's device-to-host copy
    before it returns, so the retire reads bytes already on their way."""

    def _engine(self):
        from repro.data.packets import anomaly_dataset
        from repro.forest import train_forest
        rng = np.random.default_rng(61)
        cp = ControlPlane(max_models=4, max_layers=2, max_width=8,
                          frac_bits=FRAC, max_forests=2, max_trees=4,
                          max_nodes=16, max_tree_depth=4)
        _install_zoo(cp, rng, 2, 8)
        X, y = anomaly_dataset(rng, 200, 8)
        cp.install_forest(7, train_forest(X, y, task="classify", n_trees=3,
                                          max_depth=3, max_nodes=15, seed=3))
        return cp, DataPlaneEngine(cp, max_features=8)

    @pytest.mark.parametrize("lanes", ["mlp", "forest", "both"])
    def test_copy_started_once_and_rows_match_blocking(self, lanes,
                                                       monkeypatch):
        cp, eng = self._engine()
        rng = np.random.default_rng(62)
        mids = rng.choice([100, 101, 7, 60000], 64).astype(np.int32)
        codes = rng.integers(-2000, 2000, (64, 8)).astype(np.int32)
        starts = []
        real = eng._program

        def spying(args, use_mlp, use_forest):
            prog = real(args, use_mlp, use_forest)
            return lambda *a: _CopySpy(prog(*a), starts)
        monkeypatch.setattr(eng, "_program", spying)
        fut = eng.run_features(codes, mids, block=False, lanes=lanes)
        assert len(starts) == 1
        got = np.asarray(fut)
        assert len(starts) == 1           # reading starts no second copy
        want = np.asarray(eng.run_features(codes, mids, block=True,
                                           lanes=lanes))
        assert len(starts) == 1           # a blocking call starts none
        np.testing.assert_array_equal(got, want)
        assert got.dtype == np.int32 and got.shape == (64, eng.out_features)
        assert eng.stats["packets"] == 128
        assert eng.trace_count == 1
