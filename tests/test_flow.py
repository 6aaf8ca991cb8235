"""Tentpole tests for the stateful flow engine (``src/repro/flow``): the
flow-update contract (pure-Python oracle vs the rank-round host lowering),
the FlowTable isolation property (expiry/eviction never
serves another flow's registers), the control-plane FeatureSpec family, and
the ``submit_raw()`` end-to-end bit-exactness acceptance criterion.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp

from repro.core.control_plane import ControlPlane, FeatureSpec
from repro.core.packet import encode_packets, encode_packets_np
from repro.data.packets import (RAW_HEADER_BYTES, encode_raw_headers,
                                parse_raw_headers, raw_trace)
from repro.flow import (FlowParams, FlowTable, N_FLOW_FEATURES,
                        N_FLOW_REGISTERS, reference_features)
from repro.kernels.ops import flow_update
from repro.kernels.ref import (FLOW_CODE_MAX, REG_LAST_TS, REG_PKT_COUNT,
                               flow_update_numpy)

FRAC = 8
KW = dict(frac=FRAC, ewma_shift=3, byte_shift=6, dur_shift=10)


def _random_batch(rng, n, n_slots, n_state=None, cms_shape=(2, 64),
                  monotone_ts=True):
    """A random flow-update batch over a partially pre-populated state."""
    n_state = n_state or n_slots
    state = np.zeros((n_state, N_FLOW_REGISTERS), np.int32)
    pre = rng.integers(0, n_state + 1)
    if pre:
        state[:pre] = rng.integers(0, 5000, (pre, N_FLOW_REGISTERS))
        state[:pre, REG_PKT_COUNT] = rng.integers(0, 5, pre)
    cms = rng.integers(0, 100, cms_shape).astype(np.int32)
    slots = rng.integers(0, n_slots, n).astype(np.int32)
    cells = rng.integers(0, cms_shape[1], (n, cms_shape[0])).astype(np.int32)
    if monotone_ts:
        ts = np.cumsum(rng.integers(0, 100, n)).astype(np.int32)
    else:
        ts = rng.integers(0, 10 ** 6, n).astype(np.int32)
    length = rng.integers(0, 2000, n).astype(np.int32)
    live = (rng.random(n) > 0.15).astype(np.int32)
    return state, cms, slots, cells, ts, length, live


class TestFlowUpdateKernel:
    """One contract: the scalar oracle against the host lowering."""

    def _assert_all_equal(self, args):
        want = flow_update_numpy(*args, **KW)
        got = flow_update(*args, **KW)
        for name, a, b in zip(("state", "cms", "features"), want, got):
            np.testing.assert_array_equal(a, b, err_msg=name)

    def test_fixed_case_bit_exact(self):
        rng = np.random.default_rng(0)
        self._assert_all_equal(_random_batch(rng, 300, 24))

    def test_heavy_duplication_chains_in_batch_order(self):
        """Many packets of one flow in one batch must chain their EWMAs
        sequentially — the rank-round lowering's hardest case."""
        rng = np.random.default_rng(1)
        self._assert_all_equal(_random_batch(rng, 200, 3))

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=10 ** 6),
           n_slots=st.integers(min_value=1, max_value=40),
           monotone=st.sampled_from([True, False]))
    def test_property_three_way_bit_exact(self, seed, n_slots, monotone):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(1, 150))
        self._assert_all_equal(
            _random_batch(rng, n, n_slots, monotone_ts=monotone))

    def test_empty_batch_all_backends(self):
        state = np.zeros((8, N_FLOW_REGISTERS), np.int32)
        cms = np.zeros((2, 16), np.int32)
        z = np.zeros(0, np.int32)
        for backend in ("auto", "ref"):
            s2, c2, f2 = flow_update(state, cms, z,
                                     np.zeros((0, 2), np.int32), z, z, z,
                                     backend=backend, **KW)
            np.testing.assert_array_equal(np.asarray(s2), state)
            np.testing.assert_array_equal(np.asarray(c2), cms)
            assert np.asarray(f2).shape == (0, N_FLOW_FEATURES)

    def test_dead_rows_touch_nothing(self):
        rng = np.random.default_rng(2)
        state, cms, slots, cells, ts, length, live = _random_batch(
            rng, 50, 8)
        live[:] = 0
        s2, c2, f2 = flow_update(state, cms, slots, cells, ts, length, live,
                                 **KW)
        np.testing.assert_array_equal(s2, state)
        np.testing.assert_array_equal(c2, cms)
        assert not f2.any()

    def test_ewma_reaches_fixed_point_on_periodic_flow(self):
        """A constant-period constant-length flow converges: its feature
        row stops changing — the property the steady-state serving bench
        (and the result cache) lives on."""
        n, period, ln = 64, 500, 700
        state = np.zeros((1, N_FLOW_REGISTERS), np.int32)
        cms = np.zeros((2, 64), np.int32)
        slots = np.zeros(n, np.int32)
        cells = np.zeros((n, 2), np.int32)
        ts = (np.arange(n, dtype=np.int64) * period).astype(np.int32)
        length = np.full(n, ln, np.int32)
        live = np.ones(n, np.int32)
        _, _, feats = flow_update_numpy(state, cms, slots, cells, ts,
                                        length, live, **KW)
        # len EWMA seeds at the exact value and never moves
        assert (feats[:, 3] == ln << FRAC).all()
        # IAT EWMA seeds on packet 2 at the exact period and never moves
        assert (feats[1:, 2] == period << FRAC).all()
        assert feats[0, 2] == 0

    def test_saturation_never_wraps(self):
        state = np.zeros((1, N_FLOW_REGISTERS), np.int32)
        state[0, REG_PKT_COUNT] = FLOW_CODE_MAX - 1
        state[0] = [FLOW_CODE_MAX - 1, FLOW_CODE_MAX - 1, 0, 0,
                    FLOW_CODE_MAX, FLOW_CODE_MAX, 1, FLOW_CODE_MAX >> FRAC]
        cms = np.full((1, 4), FLOW_CODE_MAX, np.int32)
        args = (state, cms, np.zeros(3, np.int32), np.zeros((3, 1), np.int32),
                np.full(3, 2 ** 31 - 1, np.int32),
                np.full(3, 65535, np.int32), np.ones(3, np.int32))
        s2, c2, f2 = flow_update_numpy(*args, **KW)
        assert (s2 >= 0).all() and (f2 >= 0).all() and (c2 >= 0).all()
        assert s2.max() <= 2 ** 31 - 1 and f2.max() <= FLOW_CODE_MAX
        self._assert_all_equal(args)

    def test_cms_estimate_upper_bounds_true_count(self):
        """Count-min never under-counts; with per-flow cells it equals the
        packet index within the flow (+ prior)."""
        rng = np.random.default_rng(3)
        state, cms, slots, cells, ts, length, live = _random_batch(
            rng, 120, 6, cms_shape=(2, 1024))
        cms[:] = 0
        live[:] = 1
        cells = np.stack([slots, slots + 512], axis=1).astype(np.int32)
        _, _, feats = flow_update_numpy(state, cms, slots, cells, ts,
                                        length, live, **KW)
        seen = {}
        for p in range(120):
            seen[int(slots[p])] = seen.get(int(slots[p]), 0) + 1
            assert feats[p, 7] >> FRAC == seen[int(slots[p])]


# ---------------------------------------------------------------------------
# FlowTable
# ---------------------------------------------------------------------------


def _keys(rng, n, key_bytes=13):
    return rng.integers(0, 256, (n, key_bytes)).astype(np.uint8)


def _packed(keys):
    return FlowTable.pack_keys(keys, 2)


class TestFlowTable:
    def test_same_key_same_slot_across_batches(self):
        rng = np.random.default_rng(0)
        t = FlowTable(2, capacity_pow2=8)
        keys = _keys(rng, 50)
        w, h = _packed(keys)
        s1, new1 = t.lookup_or_insert(w, h, np.zeros(50))
        assert new1.all() and len(t) == 50
        s2, new2 = t.lookup_or_insert(w, h, np.full(50, 10))
        np.testing.assert_array_equal(s1, s2)
        assert not new2.any()
        assert t.stats["flow_hits_total"] == 50

    def test_in_batch_duplicates_share_slot_first_is_new(self):
        rng = np.random.default_rng(1)
        t = FlowTable(2, capacity_pow2=8)
        keys = _keys(rng, 4)
        dup = keys[np.asarray([0, 1, 0, 2, 1, 0, 3])]
        w, h = _packed(dup)
        slots, new = t.lookup_or_insert(w, h, np.zeros(7))
        assert slots[0] == slots[2] == slots[5]
        assert slots[1] == slots[4]
        np.testing.assert_array_equal(new,
                                      [True, True, False, True, False,
                                       False, True])

    def test_registers_persist_for_live_flow(self):
        rng = np.random.default_rng(2)
        t = FlowTable(2, capacity_pow2=8)
        keys = _keys(rng, 3)
        w, h = _packed(keys)
        slots, _ = t.lookup_or_insert(w, h, np.zeros(3))
        t.registers[slots, REG_PKT_COUNT] = [5, 6, 7]
        slots2, new = t.lookup_or_insert(w, h, np.full(3, 100))
        assert not new.any()
        np.testing.assert_array_equal(
            t.registers[slots2, REG_PKT_COUNT], [5, 6, 7])

    def test_idle_expiry_resets_registers_in_place(self):
        rng = np.random.default_rng(3)
        t = FlowTable(2, capacity_pow2=8, idle_timeout=1000)
        w, h = _packed(_keys(rng, 2))
        slots, _ = t.lookup_or_insert(w, h, np.asarray([0, 0]))
        t.registers[slots, REG_PKT_COUNT] = 9
        t.registers[slots, REG_LAST_TS] = [0, 5000]
        _, new = t.lookup_or_insert(w, h, np.asarray([5100, 5100]))
        np.testing.assert_array_equal(new, [True, False])  # only idle flow
        assert t.registers[slots[0], REG_PKT_COUNT] == 0
        assert t.registers[slots[1], REG_PKT_COUNT] == 9
        assert t.stats["flow_expiries_total"] == 1

    def test_expire_sweep_tombstones_and_compacts(self):
        rng = np.random.default_rng(4)
        t = FlowTable(2, capacity_pow2=6, idle_timeout=100,
                      tombstone_limit=0.2)
        w, h = _packed(_keys(rng, 30))
        slots, _ = t.lookup_or_insert(w, h, np.zeros(30))
        t.registers[slots, REG_LAST_TS] = 0
        t.registers[slots, REG_PKT_COUNT] = 1
        n = t.expire(10_000)
        assert n == 30 and len(t) == 0
        assert t.stats["flow_compactions_total"] >= 1  # past tombstone_limit

    def test_eviction_when_full_restarts_flows(self):
        """Overflowing a tiny table evicts; re-arriving flows restart with
        zeroed registers — never inheriting anything."""
        rng = np.random.default_rng(5)
        t = FlowTable(2, capacity_pow2=4, load_limit=0.8)  # 16 slots
        w1, h1 = _packed(_keys(rng, 10))
        s1, _ = t.lookup_or_insert(w1, h1, np.zeros(10))
        t.registers[s1, REG_PKT_COUNT] = 77
        w2, h2 = _packed(_keys(rng, 10))  # forces eviction
        t.lookup_or_insert(w2, h2, np.ones(10))
        assert t.stats["flow_flushes_total"] >= 1 and t.generation >= 1
        s1b, new1b = t.lookup_or_insert(w1, h1, np.full(10, 2))
        assert (t.registers[s1b, REG_PKT_COUNT] <= 0).all()

    def test_want_rank_matches_slot_grouping(self):
        """The dedup-by-product rank equals within-flow occurrence order —
        the contract that lets the flow-update lowering skip re-ranking."""
        rng = np.random.default_rng(7)
        t = FlowTable(2, capacity_pow2=8)
        keys = _keys(rng, 5)
        dup = keys[np.asarray([0, 1, 0, 2, 0, 1, 3, 0])]
        w, h = _packed(dup)
        slots, is_new, rank = t.lookup_or_insert(w, h, np.zeros(8),
                                                 want_rank=True)
        assert rank is not None
        seen = {}
        for p in range(8):
            k = int(slots[p])
            assert rank[p] == seen.get(k, 0)
            seen[k] = seen.get(k, 0) + 1

    def test_gather_with_provided_rank_bit_exact(self):
        rng = np.random.default_rng(8)
        t = FlowTable(2, capacity_pow2=8)
        keys = _keys(rng, 12)
        pick = rng.integers(0, 12, 64)
        w, h = _packed(keys[pick])
        ts = np.cumsum(rng.integers(1, 50, 64)).astype(np.int32)
        slots, _, rank = t.lookup_or_insert(w, h, ts, want_rank=True)
        length = rng.integers(40, 1500, 64).astype(np.int32)
        cells = rng.integers(0, 64, (64, 2)).astype(np.int32)
        live = np.ones(64, np.int32)
        cms = np.zeros((2, 64), np.int32)
        want = flow_update_numpy(t.registers, cms, slots, cells, ts,
                                 length, live, **KW)
        got = flow_update(t.registers, cms, slots, cells, ts, length,
                          live, backend="auto", rank=rank, **KW)
        for a, b in zip(want, got):
            np.testing.assert_array_equal(a, b)

    def test_is_new_matches_zeroed_registers_under_flush_churn(self):
        """The is_new contract must survive the pathological paths too
        (probe exhaustion → mid-claim flush → retry): a packet is marked
        new exactly when its slot's registers were zeroed this call.
        max_probe=2 on a tiny table makes chain exhaustion routine."""
        rng = np.random.default_rng(11)
        t = FlowTable(2, capacity_pow2=6, max_probe=2)
        pool = _keys(rng, 40)
        for _ in range(30):
            pick = rng.integers(0, 40, int(rng.integers(1, 25)))
            w, h = _packed(pool[pick])
            slots, is_new = t.lookup_or_insert(w, h,
                                               np.zeros(pick.size))
            first = {}
            for p in range(pick.size):
                k = int(pick[p])
                if k not in first:
                    first[k] = p
                    opened = t.registers[slots[p], REG_PKT_COUNT] == 0
                    assert bool(is_new[p]) == bool(opened), \
                        (p, slots[p], is_new[p])
                else:
                    assert not is_new[p]  # only first occurrence marks
            # simulate the kernel: every touched flow now has state
            t.registers[slots, REG_PKT_COUNT] = 1
        assert t.stats["flow_flushes_total"] > 0  # the churn path actually ran

    def test_batch_beyond_load_limit_degrades_per_flow(self):
        """Hard overflow (one batch carrying more unique flows than the
        table can physically hold) rejects the overflow flows with slot
        -1 instead of raising — the served flows keep exact slots, and
        the hostile burst costs itself, not the server."""
        rng = np.random.default_rng(6)
        t = FlowTable(2, capacity_pow2=4)  # 16 slots, load limit 11
        w, h = _packed(_keys(rng, 12))
        slots, is_new = t.lookup_or_insert(w, h, np.zeros(12))
        served = slots >= 0
        assert int(served.sum()) == 11  # earliest-arriving flows win
        assert int((~served).sum()) == 1
        assert t.stats["flow_rejects_total"] == 1
        # served flows own distinct register rows and are all (re)opened
        assert np.unique(slots[served]).size == 11
        assert is_new[served].all() and not is_new[~served].any()
        # the rejected flow serves normally once the burst passes
        t2 = FlowTable(2, capacity_pow2=4)
        s2, _ = t2.lookup_or_insert(w[~served], h[~served], np.zeros(1))
        assert (s2 >= 0).all()

    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=10 ** 6),
           cap=st.integers(min_value=6, max_value=7),
           timeout=st.sampled_from([None, 500, 5000]))
    def test_property_never_another_flows_registers(self, seed, cap,
                                                    timeout):
        """THE isolation property: across hits, in-batch duplicates, idle
        expiry, compaction and wholesale eviction, the pkt_count register a
        flow observes always equals the count of *its own* packets since
        its last restart — verified against a shadow per-flow dict."""
        rng = np.random.default_rng(seed)
        t = FlowTable(2, capacity_pow2=cap, idle_timeout=timeout)
        pool = _keys(rng, 60)  # pool > load limit at cap=6: evictions occur
        shadow = {}
        now = 0
        for _ in range(12):
            n = int(rng.integers(1, 30))
            pick = rng.integers(0, pool.shape[0], n)
            keys = pool[pick]
            now += int(rng.integers(1, 3000))
            ts = np.full(n, now, np.int64)
            w, h = _packed(keys)
            slots, is_new = t.lookup_or_insert(w, h, ts)
            # apply the oracle's counting by hand (batch order)
            for p in range(n):
                k = int(pick[p])
                if is_new[p]:
                    shadow[k] = 0
                shadow[k] = shadow[k] + 1
                t.registers[slots[p], REG_PKT_COUNT] = shadow[k]
                t.registers[slots[p], REG_LAST_TS] = now
            for p in range(n):
                assert t.registers[slots[p], REG_PKT_COUNT] \
                    == shadow[int(pick[p])]
            # distinct keys in this batch never share a slot
            first = {}
            for p in range(n):
                k = int(pick[p])
                if k in first:
                    assert first[k] == slots[p]
                else:
                    first[k] = slots[p]
            assert len(set(first.values())) == len(first)


# ---------------------------------------------------------------------------
# Raw header codec
# ---------------------------------------------------------------------------


class TestRawCodec:
    def test_roundtrip(self):
        rng = np.random.default_rng(0)
        n = 100
        f = dict(src_ip=rng.integers(0, 2 ** 32, n),
                 dst_ip=rng.integers(0, 2 ** 32, n),
                 src_port=rng.integers(0, 2 ** 16, n),
                 dst_port=rng.integers(0, 2 ** 16, n),
                 proto=rng.integers(0, 256, n),
                 model_id=rng.integers(0, 2 ** 16, n),
                 ts=rng.integers(0, 2 ** 31, n),
                 length=rng.integers(0, 2 ** 16, n))
        raw = encode_raw_headers(**f)
        assert raw.shape == (n, RAW_HEADER_BYTES)
        got = parse_raw_headers(raw)
        np.testing.assert_array_equal(got.model_id, f["model_id"])
        np.testing.assert_array_equal(got.ts, f["ts"])
        np.testing.assert_array_equal(got.length, f["length"])

    def test_bad_shape_rejected(self):
        with pytest.raises(ValueError, match="raw header"):
            parse_raw_headers(np.zeros((4, RAW_HEADER_BYTES + 1), np.uint8))

    def test_reference_features_empty_trace(self):
        out = reference_features(np.zeros((0, RAW_HEADER_BYTES), np.uint8),
                                 FlowParams(frac=FRAC))
        assert out.shape == (0, N_FLOW_FEATURES)

    def test_trace_deterministic_and_sorted(self):
        a = raw_trace(np.random.default_rng(7), 500, n_flows=16,
                      model_ids=(1, 2), pattern="mixed")
        b = raw_trace(np.random.default_rng(7), 500, n_flows=16,
                      model_ids=(1, 2), pattern="mixed")
        np.testing.assert_array_equal(a, b)
        ts = parse_raw_headers(a).ts
        assert (np.diff(ts) >= 0).all()

    def test_np_encoder_matches_jax_encoder(self):
        rng = np.random.default_rng(1)
        codes = rng.integers(-2 ** 24, 2 ** 24, (64, 6)).astype(np.int32)
        mids = rng.integers(0, 2 ** 16, 64).astype(np.int32)
        flags = rng.integers(0, 256, 64).astype(np.int32)
        ocnt = rng.integers(0, 8, 64).astype(np.int32)
        want = np.asarray(encode_packets(
            jnp.asarray(mids), jnp.int32(FRAC), jnp.asarray(codes),
            flags=jnp.asarray(flags), output_cnt=jnp.asarray(ocnt)))
        got = encode_packets_np(mids, FRAC, codes, flags=flags,
                                output_cnt=ocnt)
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# FeatureSpec control-plane family
# ---------------------------------------------------------------------------


class TestFeatureSpec:
    def _cp(self):
        return ControlPlane(max_models=4, max_layers=2, max_width=8,
                            frac_bits=FRAC)

    def test_validation(self):
        cp = self._cp()
        with pytest.raises(ValueError, match="at least one column"):
            cp.install_feature_spec(1, ())
        with pytest.raises(ValueError, match="feature lanes"):
            cp.install_feature_spec(1, (0, N_FLOW_FEATURES))
        with pytest.raises(ValueError, match="input lanes"):
            cp.install_feature_spec(1, tuple(range(N_FLOW_FEATURES)) + (0,))

    def test_default_identity_mapping(self):
        cp = self._cp()
        cols, lens = cp.feature_spec_rows(np.asarray([3, 9]), 8)
        want = min(N_FLOW_FEATURES, 8)
        assert (lens == want).all()
        np.testing.assert_array_equal(cols[0, :want], np.arange(want))

    def test_install_swap_and_remove(self):
        cp = self._cp()
        v0 = cp.version
        cp.install_feature_spec(2, (7, 0, 3))
        assert cp.version == v0 + 1  # generation-swapped like tables
        cols, lens = cp.feature_spec_rows(np.asarray([2, 1]), 8)
        np.testing.assert_array_equal(cols[0, :3], [7, 0, 3])
        assert lens[0] == 3 and (cols[0, 3:] == -1).all()
        assert cols[1, 0] == 0  # id 1 keeps identity
        cp.install_feature_spec(2, (1, 1))  # hot-swap
        cols, lens = cp.feature_spec_rows(np.asarray([2]), 8)
        np.testing.assert_array_equal(cols[0, :2], [1, 1])
        assert lens[0] == 2
        assert cp.feature_spec(2) == FeatureSpec(columns=(1, 1))
        cp.remove_feature_spec(2)
        cols, lens = cp.feature_spec_rows(np.asarray([2]), 8)
        assert cols[0, 0] == 0 and lens[0] == min(N_FLOW_FEATURES, 8)

    def test_spec_survives_model_remove(self):
        cp = self._cp()
        rng = np.random.default_rng(0)
        w = rng.normal(size=(8, 2)).astype(np.float32)
        cp.install(1, [(w, np.zeros(2, np.float32))], [])
        cp.install_feature_spec(1, (4, 5))
        cp.remove(1)
        assert cp.feature_spec(1) == FeatureSpec(columns=(4, 5))


# ---------------------------------------------------------------------------
# FlowFrontend end-to-end (the acceptance criterion)
# ---------------------------------------------------------------------------


WIDTH = 8


def _server(rng, **kw):
    srv_kw = dict(max_models=8, max_layers=2, max_width=WIDTH,
                  frac_bits=FRAC, ingress_batch=256, max_forests=2,
                  max_trees=4, max_nodes=31, max_tree_depth=4)
    srv_kw.update(kw)
    from repro.launch.serve import PacketServer
    srv = PacketServer(**srv_kw)
    for mid in (1, 2):
        w1 = rng.normal(size=(WIDTH, WIDTH)).astype(np.float32) * 0.3
        w2 = rng.normal(size=(WIDTH, 2)).astype(np.float32) * 0.3
        srv.install(mid, [(w1, np.zeros(WIDTH, np.float32)),
                          (w2, np.zeros(2, np.float32))],
                    ["relu"], final_activation="sigmoid")
    return srv


def _hand_built_egress(srv, raw):
    """Oracle features → FeatureSpec gather → jax wire → blocking engine:
    the 'hand-built feature vectors' side of the acceptance check."""
    feats = reference_features(raw, FlowParams(frac=FRAC))
    fields = parse_raw_headers(raw)
    n = feats.shape[0]
    cols, lens = srv.control_plane.feature_spec_rows(fields.model_id, WIDTH)
    gathered = np.where(cols >= 0,
                        feats[np.arange(n)[:, None], np.maximum(cols, 0)], 0)
    wire = encode_packets_np(fields.model_id, FRAC, gathered,
                             feature_cnt=lens)
    return np.asarray(srv.engine.process(wire))[:, : srv.ingress.out_bytes]


class TestSubmitRawEndToEnd:
    def test_bit_exact_vs_hand_built_features(self):
        rng = np.random.default_rng(0)
        srv = _server(rng)
        srv.install_feature_spec(1, (2, 3, 4, 5))
        srv.install_feature_spec(2, (0, 7, 1, 6))
        raw = raw_trace(rng, 1500, n_flows=48, model_ids=(1, 2),
                        pattern="mixed")
        want = _hand_built_egress(srv, raw)
        for i in range(0, 1500, 500):  # ragged raw chunks
            srv.submit_raw(raw[i: i + 500])
        got = np.stack(srv.drain_packets())
        np.testing.assert_array_equal(got, want)

    def test_mlp_and_forest_share_one_flow_table(self):
        """An MLP and a forest consume different register subsets of the
        same flow table — one stateful pass, two model families."""
        from repro.data.packets import anomaly_dataset
        from repro.forest import train_forest
        rng = np.random.default_rng(1)
        srv = _server(rng)
        X, y = anomaly_dataset(rng, 512, WIDTH)
        forest = train_forest(X, y, task="classify", n_trees=4, max_depth=4,
                              max_nodes=31, seed=3)
        srv.install_forest(5, forest)
        srv.install_feature_spec(1, (2, 3))       # MLP: EWMA lanes
        srv.install_feature_spec(5, (0, 7, 1))    # forest: count lanes
        raw = raw_trace(rng, 1200, n_flows=32, model_ids=(1, 5),
                        pattern="mixed")
        want = _hand_built_egress(srv, raw)
        srv.submit_raw(raw)
        got = np.stack(srv.drain_packets())
        np.testing.assert_array_equal(got, want)
        assert len(srv.flow.table) == 32  # one shared table

    def test_spec_reinstall_zero_retraces_and_remaps_next_batch(self):
        rng = np.random.default_rng(2)
        srv = _server(rng)
        srv.install_feature_spec(1, (0, 1))
        raw = raw_trace(rng, 600, n_flows=16, model_ids=(1,),
                        pattern="periodic")
        srv.submit_raw(raw)
        srv.drain_packets()
        traces = srv.engine.trace_count
        gen0 = srv.control_plane.version
        srv.install_feature_spec(1, (3, 2))  # hot re-map live model
        assert srv.control_plane.version == gen0 + 1
        srv.submit_raw(raw)
        got = np.stack(srv.drain_packets())
        assert srv.engine.trace_count == traces  # zero retraces
        want = _hand_built_egress_second_pass(srv, raw)
        np.testing.assert_array_equal(got, want)

    def test_interleaves_with_feature_vector_chunks(self):
        """Raw and pre-encapsulated traffic share tickets and ordering."""
        rng = np.random.default_rng(3)
        srv = _server(rng)
        raw = raw_trace(rng, 300, n_flows=8, model_ids=(1,),
                        pattern="periodic")
        codes = rng.integers(-2000, 2000, (40, WIDTH)).astype(np.int32)
        wire = encode_packets_np(np.full(40, 2), FRAC, codes)
        want_wire = np.asarray(
            srv.engine.process(wire))[:, : srv.ingress.out_bytes]
        srv.submit_raw(raw[:150])
        srv.submit_packets(wire)
        srv.submit_raw(raw[150:])
        got = srv.drain_packets()
        assert len(got) == 340
        np.testing.assert_array_equal(np.stack(got[150:190]), want_wire)

    def test_engine_warm_pretraces_without_polluting_stats(self):
        rng = np.random.default_rng(9)
        srv = _server(rng)
        before = dict(srv.engine.stats)
        # a jit variant is one (batch shape, lanes) pair — warm the shape
        # the pipeline actually dispatches
        srv.engine.warm(srv.ingress.batch_size, srv.ingress.wire_bytes,
                        lanes=("mlp", "both"))
        assert srv.engine.stats == before  # warming is not traffic
        traces = srv.engine.trace_count
        raw = raw_trace(rng, 200, n_flows=8, model_ids=(1,),
                        pattern="periodic")
        srv.submit_raw(raw)
        srv.drain_packets()
        assert srv.engine.trace_count == traces  # first batch pre-traced

    def test_empty_and_malformed_raw(self):
        from repro.core.ingress import PacketError
        rng = np.random.default_rng(4)
        srv = _server(rng)
        first, n = srv.submit_raw(
            np.zeros((0, RAW_HEADER_BYTES), np.uint8))
        assert n == 0
        # a wrong-width batch degrades to per-packet error slots (it used
        # to raise away the whole submit) — the server keeps serving
        first, n = srv.submit_raw(np.zeros((4, 5), np.uint8))
        assert n == 4
        res = srv.drain_packets()
        assert len(res) == 4
        assert all(isinstance(r, PacketError) for r in res)
        assert "malformed raw header" in res[0].reason

    def test_converged_flows_short_circuit_through_result_cache(self):
        """Steady periodic traffic converges its EWMA registers; repeated
        feature rows then short-circuit (pending-window coalescing within a
        drain window, result-cache hits across windows) instead of paying
        device dispatches — the flow engine's throughput story."""
        rng = np.random.default_rng(5)
        srv = _server(rng)
        srv.install_feature_spec(1, (2, 3, 4, 5))
        raw = raw_trace(rng, 2000, n_flows=16, model_ids=(1,),
                        pattern="periodic", base_period=512)
        pipe = srv.ingress
        srv.submit_raw(raw[:1000])  # converge + populate the cache
        srv.drain_packets()
        short = pipe.cache.hits + pipe.stats["ingress_coalesced_total"]
        assert short > 900  # converged rows repeat within the window
        h0, m0 = pipe.cache.hits, pipe.cache.misses
        srv.submit_raw(raw[1000:])  # flow state continues seamlessly
        srv.drain_packets()
        dh, dm = pipe.cache.hits - h0, pipe.cache.misses - m0
        assert dh / (dh + dm) > 0.9  # cached converged rows hit directly
        assert srv.flow.flow_table_hit_rate() > 0.9
        # device work for 2000 served packets stayed a handful of batches
        assert pipe.stats["ingress_dispatched_rows_total"] <= 3 * 256


def _hand_built_egress_second_pass(srv, raw):
    """Hand-built comparison for a trace replayed as the *second* pass:
    the oracle runs the concatenated trace and keeps only the second
    half's features (flow state carries over)."""
    both = np.concatenate([raw, raw])
    feats = reference_features(both, FlowParams(frac=FRAC))[raw.shape[0]:]
    fields = parse_raw_headers(raw)
    n = feats.shape[0]
    cols, lens = srv.control_plane.feature_spec_rows(fields.model_id, WIDTH)
    gathered = np.where(cols >= 0,
                        feats[np.arange(n)[:, None], np.maximum(cols, 0)], 0)
    wire = encode_packets_np(fields.model_id, FRAC, gathered,
                             feature_cnt=lens)
    return np.asarray(srv.engine.process(wire))[:, : srv.ingress.out_bytes]
