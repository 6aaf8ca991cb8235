"""Tentpole tests for the device-resident fused serving program (PR 5):

  * host byte-codec twins: ``parse_packets_np``/``emit_results_np`` must be
    bit-identical to the in-program parser/deparser — the property that
    makes the feature-domain pipeline byte-exact with the wire path
  * the feature path (``DataPlaneEngine.run_features`` over
    ``kernels.fused_serve.serve_lanes``) equals the wire program end to end
  * the cold-traffic admission gate: unique traffic stops paying cache
    insert sweeps, reappearing duplication re-opens admission — with
    correctness invariant either way
  * load-adaptive batch sizing: the EWMA'd arrival rate picks ladder rungs,
    results stay identical, ``flush_after`` semantics survive
"""

import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp

from repro.core import packet as pk
from repro.core.control_plane import ControlPlane
from repro.core.inference import DataPlaneEngine
from repro.core.ingress import IngressPipeline
from repro.data.packets import anomaly_dataset
from repro.forest import train_forest
from repro.launch.serve import PacketServer

FRAC = 8
WIDTH = 8


def _install_mlp(cp, rng, model_id, scale=0.3):
    w1 = rng.normal(size=(WIDTH, WIDTH)).astype(np.float32) * scale
    w2 = rng.normal(size=(WIDTH, 2)).astype(np.float32) * scale
    cp.install(model_id, [(w1, np.zeros(WIDTH, np.float32)),
                          (w2, np.zeros(2, np.float32))],
               ["relu"], final_activation="sigmoid")


def _mixed_server(rng, **kw):
    kw.setdefault("max_models", 8)
    kw.setdefault("max_layers", 2)
    kw.setdefault("max_width", WIDTH)
    kw.setdefault("frac_bits", FRAC)
    kw.setdefault("max_forests", 2)
    kw.setdefault("max_trees", 4)
    kw.setdefault("max_nodes", 31)
    kw.setdefault("max_tree_depth", 4)
    srv = PacketServer(**kw)
    for mid in (1, 2):
        _install_mlp(srv.control_plane, rng, mid)
    X, y = anomaly_dataset(rng, 400, WIDTH)
    srv.install_forest(3, train_forest(X, y, task="classify", n_trees=3,
                                       max_depth=4, max_nodes=31, seed=5))
    return srv


def _wire(rng, n, model_lo=1, model_hi=4):
    mids = rng.integers(model_lo, model_hi, n).astype(np.int32)
    codes = rng.integers(-2000, 2000, (n, WIDTH)).astype(np.int32)
    return np.asarray(pk.encode_packets(jnp.asarray(mids), jnp.int32(FRAC),
                                        jnp.asarray(codes)))


class TestHostCodecTwins:
    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=10 ** 6),
           n=st.integers(min_value=1, max_value=64),
           max_features=st.integers(min_value=1, max_value=12))
    def test_parse_twin_bit_identical(self, seed, n, max_features):
        """Arbitrary wire bytes (valid or garbage): the host parser returns
        exactly the device parser's fields."""
        rng = np.random.default_rng(seed)
        length = pk.HEADER_BYTES + 4 * int(rng.integers(0, 14))
        rows = rng.integers(0, 256, (n, length)).astype(np.uint8)
        want = pk.parse_packets(jnp.asarray(rows), max_features)
        mid, fcnt, flags, feats = pk.parse_packets_np(rows, max_features)
        np.testing.assert_array_equal(mid, np.asarray(want.model_id))
        np.testing.assert_array_equal(fcnt, np.asarray(want.feature_cnt))
        np.testing.assert_array_equal(flags, np.asarray(want.flags))
        np.testing.assert_array_equal(feats, np.asarray(want.features_q))

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=10 ** 6),
           n=st.integers(min_value=1, max_value=64),
           n_out=st.integers(min_value=1, max_value=12))
    def test_emit_twin_byte_identical(self, seed, n, n_out):
        rng = np.random.default_rng(seed)
        mid = rng.integers(0, 65536, n).astype(np.int32)
        flags = rng.integers(0, 4, n).astype(np.int32)
        outs = rng.integers(-2 ** 31, 2 ** 31, (n, n_out),
                            dtype=np.int64).astype(np.int32)
        parsed = pk.ParsedBatch(
            model_id=jnp.asarray(mid), feature_cnt=jnp.zeros(n, jnp.int32),
            output_cnt=jnp.zeros(n, jnp.int32),
            scale=jnp.full((n,), FRAC, jnp.int32),
            flags=jnp.asarray(flags), features_q=jnp.zeros((n, 2), jnp.int32))
        want = np.asarray(pk.emit_results(parsed, jnp.asarray(outs), FRAC))
        got = pk.emit_results_np(mid, flags, outs, FRAC)
        np.testing.assert_array_equal(got, want)


class TestFeaturePath:
    def test_run_features_equals_wire_program(self):
        """parse_np → run_features → emit_np reproduces engine.process byte
        for byte on mixed MLP+forest traffic (including unknown ids)."""
        rng = np.random.default_rng(0)
        srv = _mixed_server(rng)
        eng = srv.engine
        wire = _wire(rng, 96, model_lo=1, model_hi=6)  # ids 4,5 unknown
        want = np.asarray(eng.process(wire))
        mid, _, flags, x0 = pk.parse_packets_np(wire, eng.max_features)
        out = np.asarray(eng.run_features(x0, mid))
        got = pk.emit_results_np(mid, flags, out, FRAC)
        np.testing.assert_array_equal(got, want[:, : got.shape[1]])

    def test_zero_retraces_across_installs_on_feature_path(self):
        rng = np.random.default_rng(1)
        srv = _mixed_server(rng)
        eng = srv.engine
        wire = _wire(rng, 32)
        mid, _, _, x0 = pk.parse_packets_np(wire, eng.max_features)
        eng.run_features(x0, mid)
        traces = eng.trace_count
        _install_mlp(srv.control_plane, rng, 1, scale=0.7)
        X, y = anomaly_dataset(rng, 256, WIDTH)
        srv.install_forest(3, train_forest(X, y, task="classify", n_trees=3,
                                           max_depth=4, max_nodes=31,
                                           seed=9))
        eng.run_features(x0, mid)
        assert eng.trace_count == traces

    def test_pipeline_results_unchanged_by_feature_staging(self):
        """The pipeline (feature-domain staging + host codec) still equals
        the wire program across ragged mixed chunks — the original PR-2
        acceptance property, now crossing the host/device codec seam."""
        rng = np.random.default_rng(2)
        srv = _mixed_server(rng, ingress_batch=64)
        chunks = [_wire(rng, n, model_lo=1, model_hi=6)
                  for n in (13, 64, 7, 100, 1)]
        for ch in chunks:
            srv.submit_packets(ch)
        got = srv.drain_packets()
        want = np.asarray(srv.engine.process(np.concatenate(chunks)))
        np.testing.assert_array_equal(
            np.stack(got), want[:, : srv.ingress.out_bytes])


class TestAdmissionGate:
    def _pipeline(self, rng, **kw):
        cp = ControlPlane(max_models=4, max_layers=2, max_width=WIDTH,
                          frac_bits=FRAC)
        for m in (1, 2):
            _install_mlp(cp, rng, m)
        eng = DataPlaneEngine(cp, max_features=WIDTH)
        return cp, eng, IngressPipeline(eng, batch_size=32, **kw)

    def test_unique_traffic_stops_insert_sweeps(self):
        rng = np.random.default_rng(5)
        cp, eng, pipe = self._pipeline(rng)
        for _ in range(8):  # sustained unique traffic: gate must close
            pipe.submit(_wire(rng, 32, model_lo=1, model_hi=3))
            pipe.flush()
        assert not pipe._admit()
        ins_before = pipe.cache.insertions
        pipe.submit(_wire(rng, 32, model_lo=1, model_hi=3))
        pipe.flush()
        # closed gate: only the 1-in-8 probe sample is admitted (the
        # re-opening detector), never the full sweep
        assert pipe.cache.insertions - ins_before \
            <= 32 // pipe._PROBE_STRIDE + 1
        # correctness is gate-independent
        pipe.reset_tickets()
        base = _wire(rng, 16, model_lo=1, model_hi=3)
        pipe.submit(base)
        got = pipe.drain()
        want = np.asarray(eng.process(base))[:, : pipe.out_bytes]
        np.testing.assert_array_equal(np.stack(got), want)

    def test_duplication_reopens_admission(self):
        rng = np.random.default_rng(6)
        cp, eng, pipe = self._pipeline(rng)
        for _ in range(8):
            pipe.submit(_wire(rng, 32, model_lo=1, model_hi=3))
            pipe.flush()
        assert not pipe._admit()
        base = _wire(rng, 32, model_lo=1, model_hi=3)
        for _ in range(3):  # dedup detects the duplication, gate re-opens
            pipe.submit(np.concatenate([base, base]))
            pipe.flush()
        assert pipe._admit()
        h0 = pipe.cache.hits
        pipe.submit(base)
        pipe.flush()
        assert pipe.cache.hits > h0  # admitted entries serve again
        pipe.drain()

    def test_cross_chunk_duplication_cannot_latch_gate_shut(self):
        """The latch-up regression: duplication that only repeats *across*
        chunks (each chunk internally unique — converged telemetry replay)
        must still re-open a closed gate, via the probe-insert samples, and
        end up serving from the cache again."""
        rng = np.random.default_rng(7)
        cp, eng, pipe = self._pipeline(rng)
        for _ in range(10):  # close the gate hard (ewma ~1e-3)
            pipe.submit(_wire(rng, 32, model_lo=1, model_hi=3))
            pipe.flush()
        assert not pipe._admit()
        base = _wire(rng, 32, model_lo=1, model_hi=3)  # internally unique
        for _ in range(40):  # resubmit the SAME chunk across windows
            pipe.submit(base)
            pipe.flush()
        assert pipe._admit()  # probe hits re-opened the gate
        h0 = pipe.cache.hits
        pipe.submit(base)
        pipe.flush()
        assert pipe.cache.hits - h0 == 32  # full cache serve again
        pipe.drain()

    def test_partial_duplication_reopens_gate(self):
        """The hysteresis fix: 20% cross-chunk duplication through a closed
        gate is observed stride-attenuated (≈ 20%/8 = 2.5% — *below* the 5%
        close threshold but 4× the true-rate image of it), so a flat
        threshold latched the gate shut forever.  The closed-state reopen
        bar is threshold/stride: the gate must come back open and serve the
        duplicated pool from cache."""
        rng = np.random.default_rng(11)
        cp, eng, pipe = self._pipeline(rng)
        for _ in range(10):  # close the gate on unique traffic
            pipe.submit(_wire(rng, 32, model_lo=1, model_hi=3))
            pipe.flush()
        assert not pipe._admit()
        pool = _wire(rng, 64, model_lo=1, model_hi=3)  # the repeating 20%
        for _ in range(60):
            dup = pool[rng.choice(64, 6, replace=False)]  # 6/32 ≈ 19%
            fresh = _wire(rng, 26, model_lo=1, model_hi=3)
            pipe.submit(np.concatenate([dup, fresh]))
            pipe.flush()
        assert pipe._admit()  # re-opened despite sub-threshold observation
        h0 = pipe.cache.hits
        pipe.submit(pool)
        pipe.flush()
        assert pipe.cache.hits - h0 >= 48  # the pool largely serves cached
        pipe.drain()

    def test_light_duplication_still_serves_probe_hits(self):
        """5% duplication sits exactly at the open-state threshold, so the
        gate may flutter — the invariant is weaker but must hold: probe
        inserts keep the duplicated rows reachable, cache hits keep
        accruing, and correctness is unchanged either way."""
        rng = np.random.default_rng(12)
        cp, eng, pipe = self._pipeline(rng)
        for _ in range(10):
            pipe.submit(_wire(rng, 32, model_lo=1, model_hi=3))
            pipe.flush()
        assert not pipe._admit()
        pool = _wire(rng, 16, model_lo=1, model_hi=3)
        hits = []
        for _ in range(80):
            dup = pool[rng.choice(16, 2, replace=False)]  # 2/32 ≈ 6%
            fresh = _wire(rng, 30, model_lo=1, model_hi=3)
            pipe.submit(np.concatenate([dup, fresh]))
            pipe.flush()
            hits.append(pipe.cache.hits)
        # the gate never latches into a no-hit regime: the second half of
        # the run keeps producing cache hits
        assert hits[-1] > hits[40]
        pipe.drain()

    def test_closed_gate_runs_cache_direct_mapped(self):
        """While unique traffic keeps the gate closed the result cache is
        direct-mapped: every row looked up and every row sampled examines
        one slot, samples replace resident entries, the answers stay
        byte-equal to the wire path, and re-opening the gate returns the
        cache to full probing."""
        rng = np.random.default_rng(13)
        # 256 slots: the open-gate fill leaves samples homes to replace
        cp, eng, pipe = self._pipeline(rng, cache_capacity_pow2=8)
        assert not pipe.cache.direct
        for _ in range(8):
            pipe.submit(_wire(rng, 32, model_lo=1, model_hi=3))
            pipe.flush()
        assert not pipe._admit() and pipe.cache.direct
        pipe.reset_tickets()
        reg = pipe.obs.registry

        def counters():
            snap = reg.snapshot()
            return [snap[k]['shard="0"'] for k in (
                "cache_probes_total", "cache_direct_rows_total",
                "cache_direct_replaced_total", "cache_flushes_total",
                "ingress_batches_total")]

        probes0, rows0, repl0, flushes0, batches0 = counters()
        chunks = [_wire(rng, 32, model_lo=1, model_hi=3) for _ in range(6)]
        for ch in chunks:
            pipe.submit(ch)
            pipe.flush()
        got = pipe.drain()
        probes, rows, repl, flushes, batches = counters()
        assert flushes == flushes0 and batches - batches0 == 6
        looked_up = 6 * 32
        sampled = 6 * len(range(0, 32, pipe._PROBE_STRIDE))
        assert probes - probes0 == rows - rows0 == looked_up + sampled
        assert repl > repl0
        want = np.asarray(eng.process(np.concatenate(chunks)))
        np.testing.assert_array_equal(np.stack(got), want[:, : pipe.out_bytes])
        base = _wire(rng, 32, model_lo=1, model_hi=3)
        for _ in range(3):
            pipe.submit(np.concatenate([base, base]))
            pipe.flush()
        assert pipe._admit() and not pipe.cache.direct
        pipe.drain()


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class TestAdaptiveBatch:
    def _pipeline(self, rng, **kw):
        cp = ControlPlane(max_models=4, max_layers=2, max_width=WIDTH,
                          frac_bits=FRAC)
        for m in (1, 2):
            _install_mlp(cp, rng, m)
        eng = DataPlaneEngine(cp, max_features=WIDTH)
        return cp, eng, IngressPipeline(eng, batch_size=1024,
                                        adaptive_batch=True, **kw)

    def test_ladder_is_static_and_bounded(self):
        rng = np.random.default_rng(7)
        _, _, pipe = self._pipeline(rng)
        assert pipe.batch_sizes == (64, 256, 1024)
        assert len(pipe.batch_sizes) <= 3

    def test_light_load_picks_small_batch(self):
        rng = np.random.default_rng(8)
        clock = _FakeClock()
        cp, eng, pipe = self._pipeline(rng, clock=clock)
        for _ in range(6):  # ~10 pkt per 5 ms → far below the small rung
            pipe.submit(_wire(rng, 10, model_lo=1, model_hi=3))
            clock.advance(0.005)
        pipe.flush()
        # every dispatch was the smallest rung, not the full 1024 batch
        assert pipe.stats["ingress_dispatched_rows_total"] \
            == pipe.stats["ingress_batches_total"] * pipe.batch_sizes[0]
        assert pipe.stats["ingress_batches_total"] >= 1

    def test_sustained_load_keeps_full_batch(self):
        rng = np.random.default_rng(9)
        clock = _FakeClock()
        cp, eng, pipe = self._pipeline(rng, clock=clock)
        for _ in range(8):  # 1024 rows every 1 ms → far above the top rung
            pipe.submit(_wire(rng, 1024, model_lo=1, model_hi=3))
            clock.advance(0.001)
        pipe.flush()
        sizes = {1024}
        assert pipe.stats["ingress_dispatched_rows_total"] >= 7 * 1024
        # after warmup the opened batches are the full rung: total padded
        # rows stay below one full batch (only the flush tail pads)
        assert pipe.stats["ingress_padded_rows_total"] < 2 * 1024
        assert sizes <= set(pipe.batch_sizes)

    def test_results_identical_with_adaptive_sizing(self):
        rng = np.random.default_rng(10)
        clock = _FakeClock()
        cp, eng, pipe = self._pipeline(rng, clock=clock)
        chunks = [_wire(rng, n, model_lo=1, model_hi=3)
                  for n in (5, 700, 31, 1500, 2)]
        for ch in chunks:
            pipe.submit(ch)
            clock.advance(0.002)
        got = pipe.drain()
        want = np.asarray(eng.process(np.concatenate(chunks)))
        np.testing.assert_array_equal(np.stack(got),
                                      want[:, : pipe.out_bytes])

    def test_flush_after_semantics_preserved(self):
        rng = np.random.default_rng(11)
        clock = _FakeClock()
        cp, eng, pipe = self._pipeline(rng, clock=clock, flush_after=0.02)
        pipe.submit(_wire(rng, 5, model_lo=1, model_hi=3))
        assert pipe.stats["ingress_batches_total"] == 0  # too young
        clock.advance(0.0199)
        assert not pipe.poll()
        clock.advance(0.0001)
        assert pipe.poll()  # age == flush_after: dispatches padded
        pipe.drain()
