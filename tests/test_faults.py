"""Fault-tolerance tests (PR 7: fault injection, shard failover with live
flow-state migration, graceful degradation, crash-safe installs).

  * the fault plan is deterministic: same seed + same event sequence →
    same firings, no wall clock or global RNG anywhere
  * transient device faults are invisible: the retry path re-dispatches
    and the drain is bit-exact with an unfaulted run
  * persistent faults degrade per-packet, never per-server: poisoned rows
    are bisected out and quarantined as ``PacketError`` slots, corrupted
    egress is caught by the model-id echo check and dropped before the
    result cache can learn it, and ``drain_packets()`` always resolves
    every ticket
  * ``install()`` / ``install_forest()`` / ``install_feature_spec()`` are
    crash-safe: a fault mid-install rolls back to the pre-install tables
    (no torn state, version unchanged, zero retraces) and a clean retry
    lands normally
  * killing 1 of 4 shards mid-stream migrates its live flows onto the
    survivors bit-exact vs the N=1 oracle, resolves every outstanding
    ticket, and costs the survivors zero retraces
  * FlowTable snapshot/restore round-trips the key→register mapping
    exactly (hypothesis), including tombstoned and restarted flows
"""

import os

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp

from repro.core import packet as pk
from repro.core.ingress import PacketError
from repro.data.packets import (RAW_HEADER_BYTES, RAW_KEY_BYTES, raw_trace,
                                validate_raw_rows)
from repro.flow.table import FlowTable
from repro.kernels.ref import REG_LAST_TS, REG_PKT_COUNT
from repro.launch.serve import PacketServer
from repro.serve import (FaultPlan, FaultSpec, InjectedFault,
                         ShardedPacketServer, chaos_plan_from_env)

FRAC = 8
WIDTH = 8
FOREVER = 1 << 60


def _install(srv, seed=7, mids=(1,)):
    rng = np.random.default_rng(seed)
    for mid in mids:
        w1 = rng.normal(size=(WIDTH, WIDTH)).astype(np.float32) * 0.3
        w2 = rng.normal(size=(WIDTH, 2)).astype(np.float32) * 0.3
        srv.install(mid, [(w1, np.zeros(WIDTH, np.float32)),
                          (w2, np.zeros(2, np.float32))],
                    ["relu"], final_activation="sigmoid")
        srv.install_feature_spec(mid, list(range(WIDTH)))
    return srv


def _plain(mids=(1,), **kw):
    kw.setdefault("max_width", WIDTH)
    kw.setdefault("frac_bits", FRAC)
    kw.setdefault("ingress_batch", 64)
    kw.setdefault("max_inflight", 2)
    return _install(PacketServer(**kw), mids=mids)


def _fabric(n, mids=(1,), **kw):
    kw.setdefault("max_width", WIDTH)
    kw.setdefault("frac_bits", FRAC)
    kw.setdefault("ingress_batch", 64)
    kw.setdefault("max_inflight", 2)
    return _install(ShardedPacketServer(n_shards=n, **kw), mids=mids)


def _trace(n, seed, n_flows=40, mids=(1,)):
    return raw_trace(np.random.default_rng(seed), n, n_flows=n_flows,
                     model_ids=mids)


def _wire(rng, n, mids):
    codes = rng.integers(-2000, 2000, (n, WIDTH)).astype(np.int32)
    return np.asarray(pk.encode_packets(
        jnp.asarray(np.asarray(mids, np.int32)), jnp.int32(FRAC),
        jnp.asarray(codes)))


def _assert_bitexact(got, want):
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert not isinstance(a, PacketError), a.reason
        assert not isinstance(b, PacketError)
        assert np.array_equal(a, b)


class TestFaultPlan:
    def test_deterministic_and_windowed(self):
        def run():
            plan = FaultPlan([FaultSpec(site="dispatch", start=2, count=3)],
                             seed=5)
            fired = []
            for i in range(10):
                try:
                    plan.fire("dispatch", shard=0)
                    fired.append(False)
                except InjectedFault:
                    fired.append(True)
            return fired
        a, b = run(), run()
        assert a == b
        assert a == [False, False, True, True, True,
                     False, False, False, False, False]

    def test_every_and_shard_scoping(self):
        plan = FaultPlan([FaultSpec(site="dispatch", shard=1, every=2,
                                    count=FOREVER)])
        hits = {0: 0, 1: 0}
        for s in (0, 1):
            for _ in range(6):
                try:
                    plan.fire("dispatch", shard=s)
                except InjectedFault:
                    hits[s] += 1
        assert hits == {0: 0, 1: 3}  # every other event, shard 1 only

    def test_corrupt_egress_deterministic(self):
        rows = np.arange(80, dtype=np.uint8).reshape(8, 10)
        p1 = FaultPlan([FaultSpec(site="egress", corrupt_frac=0.5,
                                  count=FOREVER)], seed=3)
        p2 = FaultPlan([FaultSpec(site="egress", corrupt_frac=0.5,
                                  count=FOREVER)], seed=3)
        a = p1.corrupt_egress(rows, 0)
        b = p2.corrupt_egress(rows, 0)
        assert np.array_equal(a, b)
        assert not np.array_equal(a, rows)  # something actually flipped
        changed = (a != rows).any(axis=1)
        assert 0 < int(changed.sum()) < 8  # a fraction, not everything

    def test_install_targets(self):
        srv = _plain()
        plan = FaultPlan([])
        plan.install(srv)
        assert srv.ingress.fault_plan is plan
        assert srv.control_plane.fault_plan is plan
        fab = _fabric(2)
        plan.install(fab)
        assert all(sh.pipeline.fault_plan is plan for sh in fab.shards)
        assert fab.control_plane.fault_plan is plan
        with pytest.raises(TypeError):
            plan.install(object())


class _LostResult:
    """A batch result the device lost: starting its host copy raises, and
    so does every later read of it."""

    def _lost(self, *a, **kw):
        raise RuntimeError("device lost the batch result")

    copy_to_host_async = is_ready = block_until_ready = __array__ = _lost


class TestGracefulPipeline:
    def test_transient_dispatch_fault_is_invisible(self):
        """A fault window the retry path covers: results bit-exact with an
        unfaulted server, callers never see an error."""
        raw = _trace(400, 11)
        srv = _plain()
        FaultPlan([FaultSpec(site="dispatch", start=1, count=2,
                             every=2)]).install(srv)
        ref = _plain()
        srv.submit_raw(raw)
        ref.submit_raw(raw)
        _assert_bitexact(srv.drain_packets(), ref.drain_packets())
        assert srv.ingress.stats["ingress_dispatch_retries_total"] > 0
        assert srv.ingress.stats["ingress_dispatch_failures_total"] == 0

    def test_poison_rows_bisected_and_quarantined(self):
        """A persistently-crashing batch is bisected: exactly the poison
        rows (here: everything carrying the poison model id) resolve as
        PacketError, every other row in the same batches is bit-exact."""
        srv = _plain(mids=(1, 3))
        ref = _plain(mids=(1, 3))
        FaultPlan([FaultSpec(site="dispatch", match_model_id=3,
                             count=FOREVER)]).install(srv)
        rng = np.random.default_rng(0)
        mids = np.where(rng.random(200) < 0.03, 3, 1)
        wire = _wire(rng, 200, mids)
        srv.submit_packets(wire)
        ref.submit_packets(wire)
        got, want = srv.drain_packets(), ref.drain_packets()
        assert len(got) == len(want) == 200
        n_poison = int((mids == 3).sum())
        assert n_poison > 0
        for a, b, m in zip(got, want, mids.tolist()):
            if m == 3:
                assert isinstance(a, PacketError)
                assert "quarantined" in a.reason
            else:
                assert not isinstance(a, PacketError), a.reason
                assert np.array_equal(a, b)
        assert srv.ingress.stats["ingress_quarantined_rows_total"] == n_poison
        assert srv.ingress.stats["ingress_probe_batches_total"] > 0

    def test_whole_batch_loss_degrades_not_hangs(self):
        """Every dispatch failing (no bisection can save anything) still
        resolves every ticket — as errors, never a hung drain."""
        srv = _plain()
        FaultPlan([FaultSpec(site="dispatch", count=FOREVER)]).install(srv)
        raw = _trace(150, 2)
        srv.submit_raw(raw)
        out = srv.drain_packets()
        assert len(out) == 150
        assert all(isinstance(r, PacketError) for r in out)
        assert srv.ingress.consecutive_dispatch_failures > 0

    def test_corrupted_egress_dropped_and_cache_unpolluted(self):
        """Corrupted egress rows fail the model-id echo check and resolve
        as PacketError; the corrupt batch never enters the result cache,
        so resubmitting the same packets (fault exhausted) serves the
        correct bytes."""
        rng = np.random.default_rng(4)
        srv = _plain()
        ref = _plain()
        FaultPlan([FaultSpec(site="egress", count=1,
                             corrupt_frac=0.25)]).install(srv)
        wire = _wire(rng, 64, np.ones(64, np.int64))
        srv.submit_packets(wire)
        ref.submit_packets(wire)
        got, want = srv.drain_packets(), ref.drain_packets()
        n_bad = sum(isinstance(r, PacketError) for r in got)
        assert 0 < n_bad < 64
        for a, b in zip(got, want):
            if isinstance(a, PacketError):
                assert "corrupted" in a.reason
            else:
                assert np.array_equal(a, b)
        assert srv.ingress.stats["ingress_corrupted_rows_total"] == n_bad
        # round 2: the count=1 spec is exhausted; the same bytes must now
        # serve correctly (a poisoned cache would replay the corruption)
        srv.submit_packets(wire)
        ref.submit_packets(wire)
        _assert_bitexact(srv.drain_packets(), ref.drain_packets())

    def test_stall_fault_only_slows(self):
        srv = _plain()
        FaultPlan([FaultSpec(site="stall", latency=0.002,
                             count=4)]).install(srv)
        ref = _plain()
        raw = _trace(200, 9)
        srv.submit_raw(raw)
        ref.submit_raw(raw)
        _assert_bitexact(srv.drain_packets(), ref.drain_packets())

    @pytest.mark.parametrize("lost", ["first", "every"])
    def test_lost_host_copy_salvaged_once(self, lost, monkeypatch):
        """A batch result whose host copy cannot start (nor its read
        finish) fails at retire, not at dispatch: no dispatch retry, one
        salvage per lost batch, and the engine's packet and byte counters
        match a clean run's.  Losing only the first result, the probes
        serve every row bit-exact; losing every result, each slot is the
        quarantine error a dispatch-site failure gives."""
        rng = np.random.default_rng(23)
        wire = _wire(rng, 200, np.ones(200, np.int64))  # four batches
        srv, clean = _plain(), _plain()
        ingress, eng = srv.ingress, srv.ingress.engine
        real = eng._program
        runs = []

        def losing(args, use_mlp, use_forest):
            prog = real(args, use_mlp, use_forest)

            def run(*a):
                runs.append(1)
                out = prog(*a)
                return _LostResult() if lost == "every" or len(runs) == 1 \
                    else out
            return run
        monkeypatch.setattr(eng, "_program", losing)
        salvaged = []
        real_salvage = ingress._salvage_failed_batch

        def counting(*a, **kw):
            salvaged.append(1)
            return real_salvage(*a, **kw)
        monkeypatch.setattr(ingress, "_salvage_failed_batch", counting)
        srv.submit_packets(wire)
        clean.submit_packets(wire)
        got, want = srv.drain_packets(), clean.drain_packets()
        n_batches = ingress.stats["ingress_batches_total"]
        assert n_batches == 4
        assert ingress.stats["ingress_dispatch_retries_total"] == 0
        if lost == "first":
            _assert_bitexact(got, want)
            assert len(salvaged) == 1
            assert ingress.stats["ingress_probe_batches_total"] == 1
        else:
            failed = _plain()
            FaultPlan([FaultSpec(site="dispatch",
                                 count=FOREVER)]).install(failed)
            failed.submit_packets(wire)
            slots = failed.drain_packets()
            assert len(got) == len(slots) == 200
            for a, b in zip(got, slots):
                assert isinstance(a, PacketError)
                assert isinstance(b, PacketError)
                assert a.reason == b.reason
                assert "quarantined" in a.reason
            assert len(salvaged) == n_batches
            for k in ("ingress_dispatch_failures_total",
                      "ingress_quarantined_rows_total"):
                assert ingress.stats[k] == failed.ingress.stats[k], k
            assert (failed.ingress.engine.stats["packets"]
                    == eng.stats["packets"])
        assert ingress.stats["ingress_dispatch_failures_total"] \
            == len(salvaged)
        for k in ("packets", "bytes_in", "bytes_out"):
            assert eng.stats[k] == clean.ingress.engine.stats[k], k

    @pytest.mark.parametrize("n_shards", [0, 2])
    def test_compile_error_raises_not_retried(self, n_shards):
        """A serving program that does not lower is a deployment fault: it
        raises CompileError to the caller — no retry, no bisection probe,
        no dispatch failure, no strike against a shard."""
        import jax

        from repro.core.inference import CompileError

        def refuse(*a, **kw):
            raise NotImplementedError("kernel refused by the compiler")

        srv = _fabric(n_shards) if n_shards else _plain()
        pipes = ([sh.pipeline for sh in srv.shards] if n_shards
                 else [srv.ingress])
        for p in pipes:
            p.engine._serve = jax.jit(
                refuse, static_argnames=("use_mlp", "use_forest"))
        with pytest.raises(CompileError, match="refused by the compiler"):
            srv.submit_raw(_trace(300, 5))
            srv.drain_packets()
        for p in pipes:
            for k in ("ingress_dispatch_retries_total",
                      "ingress_dispatch_failures_total",
                      "ingress_probe_batches_total"):
                assert p.stats[k] == 0, k
        if n_shards:
            faults = srv.stats()["faults"]
            assert faults["fabric_watchdog_strikes_total"] == 0
            assert faults["fabric_deaths_total"] == 0


class TestCrashSafeInstalls:
    def _forest(self):
        from repro.forest import train_forest
        rng = np.random.default_rng(1)
        X = rng.normal(size=(200, WIDTH)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.int64)
        return train_forest(X, y, task="classify", n_trees=2, max_depth=3,
                            seed=1)

    def test_install_rolls_back_clean(self):
        srv = _plain()
        rng = np.random.default_rng(8)
        wire = _wire(rng, 100, np.ones(100, np.int64))  # stateless replay
        srv.submit_packets(wire)
        want = srv.drain_packets()
        v0 = srv.control_plane.version
        traces = srv.engine.trace_count
        plan = FaultPlan([FaultSpec(site="install", count=1)])
        plan.install(srv)
        w1 = rng.normal(size=(WIDTH, WIDTH)).astype(np.float32)
        w2 = rng.normal(size=(WIDTH, 2)).astype(np.float32)
        layers = [(w1, np.zeros(WIDTH, np.float32)),
                  (w2, np.zeros(2, np.float32))]
        with pytest.raises(InjectedFault):
            srv.install(1, layers, ["relu"], final_activation="sigmoid")
        # no torn state: version unchanged, the OLD model still serves
        # bit-exact, zero retraces
        assert srv.control_plane.version == v0
        srv.submit_packets(wire)
        _assert_bitexact(srv.drain_packets(), want)
        assert srv.engine.trace_count == traces
        # the clean retry lands normally (fault exhausted) and actually
        # changes the egress
        srv.install(1, layers, ["relu"], final_activation="sigmoid")
        assert srv.control_plane.version == v0 + 1
        srv.submit_packets(wire)
        got = srv.drain_packets()
        assert any(not np.array_equal(a, b) for a, b in zip(got, want))

    def test_install_forest_and_spec_roll_back(self):
        srv = _plain()
        forest = self._forest()
        srv.install_forest(5, forest)
        v0 = srv.control_plane.version
        ids0 = srv.control_plane.installed_ids()
        plan = FaultPlan([FaultSpec(site="install", count=2)])
        plan.install(srv)
        with pytest.raises(InjectedFault):
            srv.install_forest(6, forest)
        with pytest.raises(InjectedFault):
            srv.install_feature_spec(1, [0, 1, 2, 3])
        assert srv.control_plane.version == v0
        assert srv.control_plane.installed_ids() == ids0
        # clean retries land
        srv.install_forest(6, forest)
        srv.install_feature_spec(1, [0, 1, 2, 3])
        assert srv.control_plane.version == v0 + 2

    def test_faulted_install_during_serving_window(self):
        """The mid-install fault lands between two live windows: in-flight
        and subsequent traffic keep serving the pre-install tables."""
        srv = _plain()
        ref = _plain()
        raw = _trace(300, 13)
        plan = FaultPlan([FaultSpec(site="install", count=1)])
        plan.install(srv)
        srv.submit_raw(raw[:150])
        ref.submit_raw(raw[:150])
        rng = np.random.default_rng(8)
        w1 = rng.normal(size=(WIDTH, WIDTH)).astype(np.float32)
        w2 = rng.normal(size=(WIDTH, 2)).astype(np.float32)
        with pytest.raises(InjectedFault):
            srv.install(1, [(w1, np.zeros(WIDTH, np.float32)),
                            (w2, np.zeros(2, np.float32))],
                        ["relu"], final_activation="sigmoid")
        srv.submit_raw(raw[150:])
        ref.submit_raw(raw[150:])
        _assert_bitexact(srv.drain_packets(), ref.drain_packets())


class TestRawAdmission:
    def test_validate_raw_rows_fast_path(self):
        rows = np.zeros((5, RAW_HEADER_BYTES), np.uint8)
        r, bad, reasons = validate_raw_rows(rows)
        assert bad is None and reasons is None
        assert r.shape == (5, RAW_HEADER_BYTES)

    def test_validate_raw_rows_ragged(self):
        raw = _trace(6, 1)
        rag = [row for row in raw]
        rag[2] = rag[2][:7]
        rag[4] = np.concatenate([rag[4], np.zeros(3, np.uint8)])
        rows, bad, reasons = validate_raw_rows(rag)
        assert bad.tolist() == [False, False, True, False, True, False]
        assert "7 bytes" in reasons[2] and "24 bytes" in reasons[4]
        assert np.array_equal(rows[0], raw[0])
        assert not rows[2].any()  # rejected rows are zeroed, not garbage

    def test_validate_unknown_model_ids(self):
        raw = np.ascontiguousarray(_trace(8, 2), np.uint8).copy()
        raw[3, 13:15] = [0, 9]
        rows, bad, reasons = validate_raw_rows(raw, known_model_ids={1})
        assert bad.tolist() == [False] * 3 + [True] + [False] * 4
        assert "unknown model id 9" in reasons[3]

    def test_server_interleaves_malformed_rows(self):
        """Truncated rows in a ragged submit resolve as PacketError at
        their exact submission positions; the good rows serve bit-exact
        with a server that only ever saw the good rows (rejects must not
        touch flow state)."""
        srv = _plain()
        ref = _plain()
        raw = _trace(60, 21)
        rag = [row for row in raw]
        bad_at = [5, 17, 44]
        for i in bad_at:
            rag[i] = rag[i][:10]
        srv.submit_raw(rag)
        good = np.delete(np.arange(60), bad_at)
        ref.submit_raw(raw[good])
        got = srv.drain_packets()
        want = iter(ref.drain_packets())
        assert len(got) == 60
        for i, r in enumerate(got):
            if i in bad_at:
                assert isinstance(r, PacketError)
                assert "malformed raw header" in r.reason
            else:
                assert np.array_equal(r, next(want))

    def test_strict_model_ids(self):
        srv = _plain(strict_model_ids=True)
        raw = np.ascontiguousarray(_trace(40, 3), np.uint8).copy()
        raw[5, 13:15] = [0, 9]  # never installed
        srv.submit_raw(raw)
        out = srv.drain_packets()
        assert isinstance(out[5], PacketError)
        assert "unknown model id 9" in out[5].reason
        assert sum(isinstance(r, PacketError) for r in out) == 1

    def test_flow_overflow_degrades_through_submit_raw(self):
        """Regression: a flow table sized below one ingress chunk's unique
        flows used to raise away the whole server; now the overflow flows'
        packets resolve as PacketError and the served flows are exact."""
        srv = _plain(flow_capacity_pow2=4)  # load limit 11 flows
        raw = _trace(120, 7, n_flows=30)
        first, n = srv.submit_raw(raw)  # must not raise
        assert n == 120
        out = srv.drain_packets()
        n_err = sum(isinstance(r, PacketError) for r in out)
        assert n_err > 0
        assert any("flow table overflow" in r.reason for r in out
                   if isinstance(r, PacketError))
        assert n_err < 120  # the 11 served flows' packets got real egress
        assert srv.flow.table.stats["flow_rejects_total"] > 0


class TestSnapshotRestore:
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=10 ** 6))
    def test_property_roundtrip_key_register_mapping(self, seed):
        """snapshot→restore preserves exactly the live key→register
        mapping — across claims, register churn, idle-timeout tombstones
        and in-place flow restarts — and fences the generation."""
        rng = np.random.default_rng(seed)
        t = FlowTable(2, capacity_pow2=6, idle_timeout=300)
        pool = rng.integers(0, 256, (48, RAW_KEY_BYTES)).astype(np.uint8)
        now = 0
        for step in range(int(rng.integers(2, 6))):
            now = step * 200  # some steps cross the idle timeout
            pick = rng.integers(0, 48, int(rng.integers(1, 30)))
            w, h = FlowTable.pack_keys(pool[pick], 2)
            slots, _ = t.lookup_or_insert(w, h, np.full(pick.size, now))
            ok = slots >= 0
            t.registers[slots[ok], REG_PKT_COUNT] += 1
            t.registers[slots[ok], REG_LAST_TS] = now
        t.expire(now + int(rng.integers(0, 600)))  # maybe tombstone some
        snap = t.snapshot()
        t2 = FlowTable(2, capacity_pow2=6, idle_timeout=300)
        junk = rng.integers(0, 256, (5, RAW_KEY_BYTES)).astype(np.uint8)
        jw, jh = FlowTable.pack_keys(junk, 2)
        t2.lookup_or_insert(jw, jh, np.zeros(5))  # restore must clear this
        t2.restore(snap)
        assert len(t2) == snap["keys"].shape[0]
        assert t2.generation > snap["generation"]

        def mapping(s):
            return {tuple(k): tuple(r) for k, r in
                    zip(s["keys"].tolist(), s["registers"].tolist())}
        assert mapping(t2.snapshot()) == mapping(snap)

    def test_frontend_snapshot_carries_sketch(self):
        srv = _plain()
        srv.submit_raw(_trace(200, 31))
        srv.drain_packets()
        snap = srv.flow.snapshot()
        assert snap["cms"].any()
        srv2 = _plain()
        srv2.flow.restore(snap)
        assert np.array_equal(srv2.flow.cms, srv.flow.cms)
        assert len(srv2.flow.table) == len(srv.flow.table)
        # restored server continues the flows bit-exact with the original
        raw2 = _trace(200, 31)  # same flows, next packets
        srv.submit_raw(raw2)
        srv2.submit_raw(raw2)
        _assert_bitexact(srv2.drain_packets(), srv.drain_packets())

    def test_restore_rejects_wrong_geometry(self):
        srv = _plain()
        srv.submit_raw(_trace(50, 1))
        srv.drain_packets()
        snap = srv.flow.snapshot()
        bad = dict(snap)
        bad["cms"] = np.zeros((1, 8), np.int32)
        with pytest.raises(ValueError, match="geometry"):
            srv.flow.restore(bad)


class TestFailoverDrill:
    def test_kill_one_of_four_bitexact_vs_oracle(self):
        """THE drill: 4 shards, kill one mid-stream.  Every ticket
        resolves, migrated flows continue bit-exact vs the uninterrupted
        N=1 oracle, and the survivors pay zero retraces."""
        fab = _fabric(4)
        oracle = _plain()
        raws = [_trace(300, s) for s in range(6)]
        fab.submit_raw(raws[0])   # warm every shard's jit variants
        oracle.submit_raw(raws[0])
        _assert_bitexact(fab.drain_packets(), oracle.drain_packets())
        traces0 = {s: fab.shards[s].engine.trace_count for s in range(4)}
        for i, r in enumerate(raws[1:], 1):
            fab.submit_raw(r)
            oracle.submit_raw(r)
            if i == 2:
                assert fab.kill_shard(1, "drill") is True
        got, want = fab.drain_packets(), oracle.drain_packets()
        assert len(got) == len(want) == 1500  # every ticket resolved
        _assert_bitexact(got, want)  # incl. the migrated flows' packets
        st_ = fab.stats()
        assert st_["faults"]["fabric_deaths_total"] == 1
        assert st_["faults"]["fabric_migrated_flows_total"] > 0
        assert st_["alive_shards"] == [0, 2, 3]
        for s in (0, 2, 3):  # zero retraces on survivors
            assert fab.shards[s].engine.trace_count == traces0[s]
        # the next window (all traffic re-homed) is still bit-exact
        r2 = _trace(300, 99)
        fab.submit_raw(r2)
        oracle.submit_raw(r2)
        _assert_bitexact(fab.drain_packets(), oracle.drain_packets())
        for s in (0, 2, 3):
            assert fab.shards[s].engine.trace_count == traces0[s]

    def test_cascading_deaths_down_to_last_shard(self):
        fab = _fabric(4)
        oracle = _plain()
        r = _trace(200, 42)
        fab.submit_raw(r)
        oracle.submit_raw(r)
        _assert_bitexact(fab.drain_packets(), oracle.drain_packets())
        assert fab.kill_shard(0) and fab.kill_shard(2) and fab.kill_shard(3)
        assert fab.kill_shard(1) is False  # the last shard refuses to die
        assert fab.alive_shards == [1]
        r2 = _trace(200, 43)
        fab.submit_raw(r2)
        oracle.submit_raw(r2)
        _assert_bitexact(fab.drain_packets(), oracle.drain_packets())

    def test_persistent_dispatch_faults_kill_the_shard(self):
        """A shard whose device loses whole batches repeatedly is killed
        by the supervisor; its flows fail over and the next window is
        clean."""
        fab = _fabric(2, max_consecutive_failures=2)
        FaultPlan([FaultSpec(site="dispatch", shard=0,
                             count=FOREVER)]).install(fab)
        for s in range(8):
            fab.submit_raw(_trace(200, 50 + s, n_flows=16))
        out = fab.drain_packets()
        assert len(out) == 1600
        assert fab.fault_stats["fabric_deaths_total"] == 1
        assert fab.alive_shards == [1]
        n_err = sum(isinstance(r, PacketError) for r in out)
        assert 0 < n_err < 1600  # shard-0 batches died, shard-1 served
        fab.submit_raw(_trace(200, 77, n_flows=16))
        assert not any(isinstance(r, PacketError)
                       for r in fab.drain_packets())

    def test_watchdog_stall_kills_the_shard(self):
        fab = _fabric(2, watchdog_timeout=0.01, max_consecutive_failures=2,
                      ingress_batch=32)
        FaultPlan([FaultSpec(site="stall", shard=0, latency=0.05,
                             count=FOREVER)]).install(fab)
        for s in range(10):
            fab.submit_raw(_trace(120, 60 + s, n_flows=8))
        fab.drain_packets()
        assert fab.fault_stats["fabric_watchdog_strikes_total"] >= 2
        assert fab.fault_stats["fabric_deaths_total"] == 1
        assert fab.alive_shards == [1]

    def test_round_robin_skips_dead_shards(self):
        fab = _fabric(3)
        rng = np.random.default_rng(6)
        fab.kill_shard(1)
        for _ in range(6):
            fab.submit_packets(_wire(rng, 8, np.ones(8, np.int64)))
        out = fab.drain_packets()
        assert len(out) == 48
        assert not any(isinstance(r, PacketError) for r in out)
        assert fab.shards[1].pipeline.stats["ingress_packets_total"] == 0

    def test_fabric_admission_rejects_malformed(self):
        fab = _fabric(2)
        raw = _trace(50, 5)
        rag = [row for row in raw]
        rag[7] = rag[7][:10]
        fab.submit_raw(rag)
        out = fab.drain_packets()
        assert isinstance(out[7], PacketError)
        assert "malformed raw header" in out[7].reason
        assert sum(isinstance(r, PacketError) for r in out) == 1
        assert fab.fault_stats["fabric_rejected_rows_total"] == 1


class TestChaosEnv:
    def test_chaos_plan_off_by_default(self, monkeypatch):
        monkeypatch.delenv("REPRO_CHAOS", raising=False)
        assert chaos_plan_from_env() is None

    def test_chaos_mode_is_transparent(self, monkeypatch):
        """REPRO_CHAOS=1 (the CI chaos lane): every pipeline self-installs
        a transient dispatch plan whose firings the retry path swallows —
        serving stays bit-exact with a chaos-free server."""
        ref = _plain()
        monkeypatch.setenv("REPRO_CHAOS", "1")
        monkeypatch.setenv("REPRO_CHAOS_EVERY", "3")  # fire often
        srv = _plain()
        assert srv.ingress.fault_plan is not None
        raw = _trace(400, 17)
        srv.submit_raw(raw)
        ref.submit_raw(raw)
        _assert_bitexact(srv.drain_packets(), ref.drain_packets())
        assert srv.ingress.stats["ingress_dispatch_retries_total"] > 0
