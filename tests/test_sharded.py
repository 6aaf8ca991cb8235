"""Sharded serving fabric tests (PR 6: N-shard refactor).

  * RSS dispatch is a pure function: every 5-tuple maps to exactly one
    shard, stably across re-dispatch (the flow-affinity precondition)
  * flow affinity holds end to end: each flow's FlowTable entry lives on
    exactly one shard
  * a mixed ``submit_raw``/``submit_packets`` trace served sharded is
    bit-exact with the single-engine server, in exact per-packet
    submission order, for N = 1, 2 and 4 (N=1 is the degenerate case that
    lets the whole tier-1 suite double as the fabric's oracle)
  * the cross-shard generation fence: ``install()`` / ``remove()`` /
    ``install_feature_spec()`` during a sharded serving window never tear
    (every packet's egress is computed wholly under one generation, equal
    to the single-engine reference running the same sequence) and cost
    zero retraces on every shard
"""

import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp

from repro.core import packet as pk
from repro.data.packets import (RAW_KEY_BYTES, encode_raw_headers,
                                parse_raw_headers, raw_trace)
from repro.flow.table import FlowTable
from repro.launch.serve import PacketServer
from repro.serve import ShardedPacketServer, rss_shard

FRAC = 8
WIDTH = 8
KEY_WORDS = (RAW_KEY_BYTES + 7) // 8


def _install(srv, seed=7):
    rng = np.random.default_rng(seed)
    w1 = rng.normal(size=(WIDTH, WIDTH)).astype(np.float32) * 0.3
    w2 = rng.normal(size=(WIDTH, 2)).astype(np.float32) * 0.3
    srv.install(1, [(w1, np.zeros(WIDTH, np.float32)),
                    (w2, np.zeros(2, np.float32))],
                ["relu"], final_activation="sigmoid")
    srv.install_feature_spec(1, list(range(WIDTH)))
    return srv


def _plain(**kw):
    kw.setdefault("max_width", WIDTH)
    kw.setdefault("frac_bits", FRAC)
    kw.setdefault("ingress_batch", 64)
    kw.setdefault("max_inflight", 2)
    return _install(PacketServer(**kw))


def _fabric(n, **kw):
    kw.setdefault("max_width", WIDTH)
    kw.setdefault("frac_bits", FRAC)
    kw.setdefault("ingress_batch", 64)
    kw.setdefault("max_inflight", 2)
    return _install(ShardedPacketServer(n_shards=n, **kw))


def _wire(rng, n):
    mids = np.ones(n, np.int32)
    codes = rng.integers(-2000, 2000, (n, WIDTH)).astype(np.int32)
    return np.asarray(pk.encode_packets(jnp.asarray(mids), jnp.int32(FRAC),
                                        jnp.asarray(codes)))


def _key_hash(src_ip, dst_ip, sport, dport, proto):
    raw = encode_raw_headers(
        np.array([src_ip]), np.array([dst_ip]), np.array([sport]),
        np.array([dport]), np.array([proto]), np.array([1]),
        np.array([0]), np.array([64]))
    fields = parse_raw_headers(raw)
    _, hashes = FlowTable.pack_keys(fields.key_bytes, KEY_WORDS)
    return hashes


class TestRSSDispatch:
    @given(src_ip=st.integers(0, 2 ** 32 - 1),
           dst_ip=st.integers(0, 2 ** 32 - 1),
           sport=st.integers(0, 65535), dport=st.integers(0, 65535),
           proto=st.integers(0, 255),
           n_shards=st.sampled_from([1, 2, 3, 4, 8]))
    @settings(max_examples=60, deadline=None)
    def test_every_tuple_maps_to_exactly_one_stable_shard(
            self, src_ip, dst_ip, sport, dport, proto, n_shards):
        h = _key_hash(src_ip, dst_ip, sport, dport, proto)
        s1 = rss_shard(h, n_shards)
        s2 = rss_shard(h, n_shards)  # re-dispatch: must be stable
        assert s1.shape == (1,)
        assert 0 <= int(s1[0]) < n_shards
        assert int(s1[0]) == int(s2[0])

    def test_dispatch_is_per_flow_constant(self):
        """Every packet of a flow routes to the same shard — duplicated
        key rows inside one batch and across batches agree."""
        rng = np.random.default_rng(0)
        srv = _fabric(4)
        raw = raw_trace(rng, 2000, n_flows=32, model_ids=(1,))
        d1 = srv.dispatch_shards(raw)
        d2 = srv.dispatch_shards(raw)  # stateless: identical on re-dispatch
        np.testing.assert_array_equal(d1, d2)
        assert d1.min() >= 0 and d1.max() < 4
        fields = parse_raw_headers(raw)
        keys = [bytes(k) for k in fields.key_bytes]
        seen = {}
        for k, s in zip(keys, d1.tolist()):
            assert seen.setdefault(k, s) == s

    def test_flow_affinity_end_to_end(self):
        """After serving, each flow's register entry exists on exactly one
        shard: per-shard FlowTable populations partition the flow set."""
        rng = np.random.default_rng(1)
        srv = _fabric(4)
        raw = raw_trace(rng, 3000, n_flows=48, model_ids=(1,))
        shard_ids = srv.dispatch_shards(raw)
        srv.submit_raw(raw)
        srv.drain_packets()
        fields = parse_raw_headers(raw)
        keys = [bytes(k) for k in fields.key_bytes]
        per_shard_flows = [set() for _ in range(4)]
        for k, s in zip(keys, shard_ids.tolist()):
            per_shard_flows[s].add(k)
        for sh, flows in zip(srv.shards, per_shard_flows):
            assert len(sh.flow.table) == len(flows)
        assert sum(len(f) for f in per_shard_flows) == 48

    def test_dispatch_shards_follows_the_route_after_a_kill(self):
        """After a shard dies, ``dispatch_shards`` names the shard that
        ``submit_raw`` now routes each flow to: never the dead one, and
        the RSS home for every flow the kill did not touch."""
        rng = np.random.default_rng(4)
        srv = _fabric(4)
        raw = raw_trace(rng, 2000, n_flows=64, model_ids=(1,))
        before = srv.dispatch_shards(raw)
        assert (before == 1).any()
        assert srv.kill_shard(1)
        after = srv.dispatch_shards(raw)
        assert not (after == 1).any()
        np.testing.assert_array_equal(after[before != 1],
                                      before[before != 1])
        srv.submit_raw(raw)
        srv.drain_packets()
        fields = parse_raw_headers(raw)
        per_shard_flows = [set() for _ in range(4)]
        for k, s in zip(fields.key_bytes, after.tolist()):
            per_shard_flows[s].add(bytes(k))
        for s in srv.alive_shards:
            assert len(srv.shards[s].flow.table) == len(per_shard_flows[s])
        assert sum(len(f) for f in per_shard_flows) == 64


class TestShardedBitExact:
    def _mixed_run(self, srv, rng):
        """Interleave raw-header batches and encapsulated wire chunks."""
        raws = [raw_trace(rng, n, n_flows=40, model_ids=(1,))
                for n in (500, 300, 700)]
        wires = [_wire(rng, n) for n in (90, 150)]
        srv.submit_raw(raws[0])
        srv.submit_packets(wires[0])
        srv.submit_raw(raws[1])
        srv.submit_packets(wires[1])
        srv.submit_raw(raws[2])
        return srv.drain_packets()

    def test_mixed_trace_bit_exact_vs_single_engine(self):
        rng = np.random.default_rng(2)
        ref = self._mixed_run(_plain(), np.random.default_rng(3))
        for n in (1, 2, 4):
            out = self._mixed_run(_fabric(n), np.random.default_rng(3))
            assert len(out) == len(ref)
            for i, (a, b) in enumerate(zip(out, ref)):
                np.testing.assert_array_equal(
                    a, b, err_msg=f"n_shards={n} packet {i}")

    @given(seed=st.integers(0, 10 ** 6))
    @settings(max_examples=3, deadline=None)
    def test_raw_trace_order_property(self, seed):
        """Property form: any mixed raw trace drains sharded bit-exact with
        N=1, in per-packet submission order."""
        rng = np.random.default_rng(seed)
        raw = raw_trace(rng, 400, n_flows=24, model_ids=(1,))
        one = _fabric(1, ingress_batch=32)
        two = _fabric(2, ingress_batch=32)
        one.submit_raw(raw)
        two.submit_raw(raw)
        r1 = one.drain_packets()
        r2 = two.drain_packets()
        assert len(r1) == len(r2) == 400
        for a, b in zip(r1, r2):
            np.testing.assert_array_equal(a, b)


class TestCrossShardInstallFence:
    def test_install_remove_respec_never_tear_zero_retraces(self):
        """Hot ops mid-window: weight reinstall, feature-spec remap and
        remove() land between arrival batches under the fabric fence —
        every packet's egress equals the single-engine reference running
        the identical sequence (no packet sees torn generations), and no
        shard retraces after warmup."""
        rng_trace = np.random.default_rng(5)
        phases = [raw_trace(rng_trace, 250, n_flows=20, model_ids=(1,))
                  for _ in range(4)]
        wrng = np.random.default_rng(11)
        w1b = wrng.normal(size=(WIDTH, WIDTH)).astype(np.float32) * 0.25
        w2b = wrng.normal(size=(WIDTH, 2)).astype(np.float32) * 0.25
        respec = [WIDTH - 1 - i for i in range(WIDTH)]  # reversed lanes

        def run(srv, flush, shards):
            # warmup: compile each shard's serving program once
            warm = raw_trace(np.random.default_rng(9), 200, n_flows=20,
                             model_ids=(1,))
            srv.submit_raw(warm)
            srv.drain_packets()
            tc0 = [sh.trace_count for sh in shards]
            srv.submit_raw(phases[0])
            flush()
            srv.install(1, [(w1b, np.zeros(WIDTH, np.float32)),
                            (w2b, np.zeros(2, np.float32))],
                        ["relu"], final_activation="sigmoid")
            srv.submit_raw(phases[1])
            flush()
            srv.install_feature_spec(1, respec)
            srv.submit_raw(phases[2])
            flush()
            srv.remove(1)
            srv.submit_raw(phases[3])
            out = srv.drain_packets()
            tc1 = [sh.trace_count for sh in shards]
            return out, tc0, tc1

        plain = _plain()
        ref, _, _ = run(plain, plain.ingress.flush, [plain.engine])

        for n in (2, 4):
            fab = _fabric(n)

            def flush():
                for sh in fab.shards:
                    sh.pipeline.flush()

            out, tc0, tc1 = run(fab, flush,
                                [sh.engine for sh in fab.shards])
            assert tc1 == tc0, f"retrace on a shard at n_shards={n}"
            assert len(out) == len(ref)
            for i, (a, b) in enumerate(zip(out, ref)):
                np.testing.assert_array_equal(
                    a, b, err_msg=f"n_shards={n} packet {i}")

    def test_generation_atomic_across_shards(self):
        """One shared control plane ⇒ one generation counter: after any
        install, every shard's next dispatch reads the same version (there
        is no per-shard generation to diverge)."""
        fab = _fabric(4)
        v0 = fab.control_plane.version
        rng = np.random.default_rng(6)
        w1 = rng.normal(size=(WIDTH, WIDTH)).astype(np.float32) * 0.2
        w2 = rng.normal(size=(WIDTH, 2)).astype(np.float32) * 0.2
        fab.install(2, [(w1, np.zeros(WIDTH, np.float32)),
                        (w2, np.zeros(2, np.float32))], ["relu"])
        assert fab.control_plane.version == v0 + 1
        assert all(sh.pipeline.cp is fab.control_plane
                   for sh in fab.shards)
        assert all(sh.engine.cp is fab.control_plane for sh in fab.shards)


def _counters(srv, suffix="_seconds_total"):
    snap = srv.obs.registry.snapshot()
    return {k: v for k, v in snap.items() if k.endswith(suffix)}


class TestFabricSpans:
    def test_only_a_fabric_registers_route_and_merge(self):
        fab = _counters(_fabric(4))
        for name in ("fabric_route_seconds_total",
                     "fabric_merge_seconds_total"):
            assert fab[name] == {'shard="-1"': 0.0}
        plain = _counters(_plain())
        assert not {"fabric_route_seconds_total",
                    "fabric_merge_seconds_total"} & set(plain)

    def test_route_and_merge_advance(self):
        srv = _fabric(4)
        raw = raw_trace(np.random.default_rng(12), 1000, n_flows=40,
                        model_ids=(1,))
        srv.submit_raw(raw)
        c = _counters(srv)
        route = c["fabric_route_seconds_total"]['shard="-1"']
        assert route > 0
        assert c["fabric_merge_seconds_total"]['shard="-1"'] == 0
        srv.drain_packets()
        c = _counters(srv)
        assert c["fabric_merge_seconds_total"]['shard="-1"'] > 0
        assert c["fabric_route_seconds_total"]['shard="-1"'] == route

    def test_route_leaves_out_the_shards_and_layers_fit_wall_time(
            self, monkeypatch):
        """A shard's own submit is outside ``fabric.route``: 50 ms slept
        in each shard's ``flow.submit_raw``, under no span, never reaches
        the route counter; and all layer counters together stay inside the
        wall time of the submits and drains."""
        import time

        from repro.flow.frontend import FlowFrontend
        submit = FlowFrontend.submit_raw

        def slow(self, *a, **kw):
            time.sleep(0.05)
            return submit(self, *a, **kw)
        monkeypatch.setattr(FlowFrontend, "submit_raw", slow)
        srv = _fabric(4)
        rng = np.random.default_rng(13)
        wall = 0.0
        for _ in range(3):
            raw = raw_trace(rng, 512, n_flows=40, model_ids=(1,))
            t0 = time.perf_counter()
            srv.submit_raw(raw)
            srv.drain_packets()
            wall += time.perf_counter() - t0
        c = _counters(srv)
        assert len(set(srv.dispatch_shards(raw).tolist())) == 4
        assert c["fabric_route_seconds_total"]['shard="-1"'] < 0.05
        total = sum(sum(v.values()) for v in c.values())
        assert 0 < total <= wall
        assert wall - total >= 3 * 4 * 0.05 * 0.99
