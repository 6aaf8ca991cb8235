"""Observability-layer tests (PR 8: metrics registry, latency histograms,
packet-lifecycle tracing, structured event log; layer spans).

  * histogram percentile readout is within one log-bucket ratio of
    ``np.percentile(..., method="inverted_cdf")`` on arbitrary positive
    samples (hypothesis), and exact on degenerate/overflow inputs
  * packet-lifecycle tracing samples deterministically (1-in-N by ticket
    id), decomposes end-to-end latency into queue/batch/inflight/drain,
    and never causes a retrace
  * layer spans add self time (less nested spans) to per-shard
    ``<layer>_seconds_total`` counters, time exactly the layers a surface
    runs (raw: the flow engine; wire: the wire parse), and annotate the
    profiler's trace only while a profiler session runs
  * the event log is ordered, bounded, and reconstructs the full
    kill-1-of-4 failover drill post-hoc: installs → watchdog strikes →
    fault firings → shard kill → flow migrations, in sequence order
  * every chaos-lane (``REPRO_CHAOS=1``) fault firing appears in the
    event log — one ``fault_injected`` record per ``plan.fired`` entry
  * the Prometheus text exposition round-trips against the registry
    snapshot value-for-value
  * stats adapters speak only the canonical ``<subsystem>_<noun>_total``
    registry cells (the PR-8 one-release legacy aliases are gone)
  * ``ShardedPacketServer.stats()`` never blocks on the fabric lock — a
    poll during a long submit completes immediately (regression)
"""

import threading

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp

from repro.core import packet as pk
from repro.core.ingress import PacketError
from repro.data.packets import raw_trace
from repro.launch.serve import PacketServer
from repro.obs import (LAYER_SPANS, EventLog, Histogram, MetricsRegistry,
                       Observability, PacketTracer, StatsAdapter)
from repro.serve import FaultPlan, FaultSpec, ShardedPacketServer

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


def _dup_wire(seed, n=512):
    """Encapsulated wire batch where the second half byte-repeats the
    first (50% duplicates — exercises the cache/coalesce short-circuit)."""
    rng = np.random.default_rng(seed)
    codes = rng.integers(-2000, 2000, (n // 2, WIDTH)).astype(np.int32)
    codes = np.concatenate([codes, codes])
    mids = np.ones(n, np.int32)
    return np.asarray(pk.encode_packets(
        jnp.asarray(mids), jnp.int32(FRAC), jnp.asarray(codes)))


class TestHistogram:
    @settings(max_examples=60, deadline=None)
    @given(vals=st.lists(st.floats(min_value=1e-5, max_value=50.0),
                         min_size=1, max_size=300),
           q=st.integers(min_value=0, max_value=100))
    def test_property_percentile_within_one_bucket(self, vals, q):
        """The documented contract: the readout is the upper edge of the
        inverted-CDF order statistic's bucket (clamped to the observed
        extremes), so true <= readout <= true * 10**(1/bpd)."""
        h = Histogram(lo=1e-6, hi=100.0, buckets_per_decade=60)
        h.observe_many(np.asarray(vals))
        got = h.percentile(q)
        true = float(np.percentile(vals, q, method="inverted_cdf"))
        ratio = 10.0 ** (1.0 / 60)
        assert true * (1 - 1e-12) <= got <= true * ratio * (1 + 1e-12)

    def test_single_value_is_exact(self):
        h = Histogram()
        for _ in range(10):
            h.observe(0.012345)
        for q in (0, 50, 99, 100):
            assert h.percentile(q) == 0.012345

    def test_overflow_bucket_reports_the_max(self):
        h = Histogram(lo=1e-6, hi=1.0)
        h.observe_many(np.asarray([0.5, 3.0, 7.0]))  # two past hi
        assert h.percentile(99) == 7.0
        assert h.summary()["max"] == 7.0

    def test_empty_histogram(self):
        h = Histogram()
        assert np.isnan(h.percentile(50))
        assert h.summary() == {"count": 0, "sum": 0.0}

    def test_observe_paths_agree(self):
        a, b = Histogram(), Histogram()
        vals = np.geomspace(1e-5, 10.0, 257)
        for v in vals:
            a.observe(float(v))
        b.observe_many(vals)
        assert np.array_equal(a.bucket_counts, b.bucket_counts)
        assert a.count == b.count == 257
        assert a.percentile(90) == b.percentile(90)


class TestTracer:
    def _serve(self, trace_every):
        srv = _plain(trace_every=trace_every)
        wire = _dup_wire(3)
        for i in range(0, len(wire), 64):
            srv.submit_packets(wire[i: i + 64])
        srv.drain_packets()
        return srv

    def test_sampling_is_deterministic(self):
        """Two identical runs trace exactly the same tickets with the same
        short-circuit classification."""
        a, b = self._serve(8), self._serve(8)
        sa, sb = a.obs.spans(), b.obs.spans()
        assert [s["ticket"] for s in sa] == [s["ticket"] for s in sb]
        assert ([s["short_circuit"] for s in sa]
                == [s["short_circuit"] for s in sb])
        assert sorted(s["ticket"] for s in sa) == list(range(0, 512, 8))
        # the duplicate half short-circuits (cache/coalesce), the fresh
        # half pays the device
        assert any(s["short_circuit"] for s in sa)
        assert any(not s["short_circuit"] for s in sa)

    def test_spans_decompose_end_to_end_latency(self):
        srv = self._serve(16)
        spans = srv.obs.spans()
        assert spans
        for s in spans:
            assert s["total_s"] >= 0.0
            assert s["total_s"] == pytest.approx(s["retire"] - s["submit"])
            if not s["short_circuit"]:
                parts = (s["queue_s"] + s["batch_s"] + s["inflight_s"]
                         + s["drain_s"])
                assert parts == pytest.approx(s["total_s"], abs=1e-9)
        assert all(t.open_spans == 0 for t in srv.obs.tracers)

    def test_tracing_never_retraces(self):
        plain, traced = self._serve(0), self._serve(8)
        assert traced.engine.trace_count == plain.engine.trace_count
        assert plain.obs.spans() == []  # off by default stays off

    def test_fake_clock_makes_spans_deterministic(self):
        ticks = iter(np.arange(0.0, 1e6, 1.0))
        tr = PacketTracer(every=2, clock=lambda: float(next(ticks)))
        tr.on_submit(np.arange(4))
        tr.on_stage(np.asarray([0, 2]), np.asarray([0, 1]))
        tr.on_dispatch(np.asarray([0, 1]))
        tr.on_result_ready(np.asarray([0, 1]))
        tr.on_retire(np.arange(4))
        spans = tr.spans()
        assert [s["ticket"] for s in spans] == [0, 2]
        assert all(s["queue_s"] == 1.0 and s["batch_s"] == 1.0
                   and s["inflight_s"] == 1.0 and s["drain_s"] == 1.0
                   and s["result_ready"] == 3.0 for s in spans)
        assert "device_s" not in spans[0]


class _Ticks:
    """Fake clock: each read returns the current time."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _seconds(obs, name, shard=0):
    snap = obs.registry.snapshot()
    return snap[name.replace(".", "_") + "_seconds_total"][f'shard="{shard}"']


class TestLayerSpans:
    def test_nested_spans_count_self_time(self):
        clk = _Ticks()
        obs = Observability(clock=clk)
        with obs.span("ingress.ingest"):
            clk.t += 1.0
            with obs.span("ingress.stage"):
                clk.t += 2.0
                with obs.span("ingress.dispatch"):
                    clk.t += 4.0
                clk.t += 8.0
            clk.t += 16.0
            with obs.span("ingress.stage"):
                clk.t += 32.0
        assert _seconds(obs, "ingress.ingest") == 17.0
        assert _seconds(obs, "ingress.stage") == 2.0 + 8.0 + 32.0
        assert _seconds(obs, "ingress.dispatch") == 4.0
        # siblings add up to the outermost span's duration
        assert sum(_seconds(obs, n) for n in (
            "ingress.ingest", "ingress.stage", "ingress.dispatch")) == 63.0

    def test_an_exception_closes_the_span(self):
        clk = _Ticks()
        obs = Observability(clock=clk)
        with pytest.raises(KeyError):
            with obs.span("egress.resolve"):
                clk.t += 1.0
                with obs.span("egress.encode"):
                    clk.t += 2.0
                    raise KeyError("x")
        with obs.span("egress.encode"):
            clk.t += 4.0
        assert _seconds(obs, "egress.resolve") == 1.0
        assert _seconds(obs, "egress.encode") == 6.0

    def test_counter_names_and_shard_labels(self):
        fab = _fabric(2)
        text = fab.obs.to_prometheus_text()
        for name in LAYER_SPANS:
            counter = name.replace(".", "_") + "_seconds_total"
            assert f"# TYPE {counter} counter" in text
            for shard in (0, 1):
                assert f'{counter}{{shard="{shard}"}} 0' in text
        assert 'flow_lookup_seconds_total{shard="1"} 0' in text
        assert 'ingress_device_wait_seconds_total{shard="0"} 0' in text
        fab.submit_raw(_trace(256, 3))
        fab.drain_packets()
        snap = fab.obs.registry.snapshot()["flow_lookup_seconds_total"]
        assert set(snap) == {'shard="0"', 'shard="1"'}
        assert all(v > 0 for v in snap.values())

    def test_raw_traffic_times_the_flow_engine(self):
        # a 64-slot flow table under 200 flows: expiry, compaction and
        # wholesale eviction all run
        srv = _plain(flow_capacity_pow2=6, flow_idle_timeout=50)
        raw = _trace(2048, 5, n_flows=200)
        for i in range(0, raw.shape[0], 32):
            srv.submit_raw(raw[i: i + 32])
        srv.drain_packets()
        for name in LAYER_SPANS:
            got = _seconds(srv.obs, name)
            if name == "ingress.parse":
                assert got == 0, name
            elif name.startswith(("flow.", "ingress.", "egress.")):
                assert got > 0, name
        assert srv.flow.table.stats["flow_compactions_total"] \
            + srv.flow.table.stats["flow_flushes_total"] > 0

    def test_wire_traffic_times_the_wire_parse(self):
        srv = _plain()
        wire = _dup_wire(4)
        for i in range(0, len(wire), 64):
            srv.submit_packets(wire[i: i + 64])
        srv.drain_packets()
        for name in LAYER_SPANS:
            got = _seconds(srv.obs, name)
            if name.startswith("flow."):
                assert got == 0, name
            elif name in ("ingress.parse", "ingress.ingest",
                          "ingress.stage", "ingress.dispatch",
                          "ingress.device_wait", "egress.encode",
                          "egress.resolve"):
                assert got > 0, name

    def test_annotations_only_while_a_profiler_runs(self, monkeypatch):
        made = []
        enabled = [False]

        class FakeAnnotation:
            def __init__(self, name):
                made.append(name)

            @staticmethod
            def is_enabled():
                return enabled[0]

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        srv = _plain()
        monkeypatch.setattr(srv.obs.layer_spans, "_annotation",
                            FakeAnnotation)
        raw = _trace(256, 6)
        srv.submit_raw(raw)
        srv.drain_packets()
        assert made == []
        assert _seconds(srv.obs, "flow.lookup") > 0
        enabled[0] = True
        srv.submit_raw(raw)
        srv.drain_packets()
        assert made and all(n.startswith("repro.") for n in made)
        assert {"repro.flow.lookup", "repro.ingress.device_wait",
                "repro.egress.encode"} <= set(made)
        assert set(made) <= {"repro." + n for n in LAYER_SPANS}

    def test_spans_never_retrace(self):
        srv = _plain()
        srv.submit_raw(_trace(256, 7))
        srv.drain_packets()
        n = srv.engine.trace_count
        srv.submit_raw(_trace(256, 8))
        srv.drain_packets()
        assert srv.engine.trace_count == n


class TestEventLog:
    def test_ring_bound_and_dropped(self):
        log = EventLog(capacity=4)
        for i in range(10):
            log.emit("install", slot=i)
        assert len(log) == 4
        assert log.dropped == 6
        assert [e.seq for e in log.records()] == [6, 7, 8, 9]
        assert [e.detail["slot"] for e in log.records()] == [6, 7, 8, 9]

    def test_timestamps_use_injected_clock(self):
        ticks = iter([10.0, 20.0, 30.0])
        log = EventLog(clock=lambda: next(ticks))
        log.emit("gate_closed", shard=2)
        log.emit("gate_open", shard=2)
        a, b = log.records()
        assert (a.ts, b.ts) == (10.0, 20.0)
        assert log.last("gate_open") is b
        assert log.counts() == {"gate_closed": 1, "gate_open": 1}


class TestFailoverDrillEventLog:
    def test_kill_one_of_four_reconstructs_from_log(self):
        """THE drill, read back from telemetry alone: installs, watchdog
        strikes, fault firings, the shard kill and every flow migration
        appear in the event log in sequence order."""
        fab = _fabric(4, watchdog_timeout=1e-12)
        # phase 1: the absurd watchdog timeout makes every healthy submit
        # a strike (2 per shard — below the kill threshold of 3)
        for s in (11, 12):
            fab.submit_raw(_trace(200, s))
        fab.drain_packets()
        fab.watchdog_timeout = None
        strikes = fab.obs.events.records("watchdog_strike")
        assert strikes and all(0 <= e.shard < 4 for e in strikes)
        assert 1 in fab.alive_shards
        seq0 = fab.obs.events.records()[-1].seq  # phase boundary
        # phase 2: persistent dispatch faults on shard 1 only -> the
        # supervisor kills it and migrates its flows to the survivors
        FaultPlan([FaultSpec(site="dispatch", shard=1,
                             count=FOREVER)]).install(fab)
        for s in range(10):
            fab.submit_raw(_trace(400, 20 + s, n_flows=16))
            if 1 not in fab.alive_shards:
                break
        out = fab.drain_packets()
        assert 1 not in fab.alive_shards
        assert len(out) > 0

        ev = fab.obs.events
        seqs = [e.seq for e in ev.records()]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
        installs = (ev.records("install")
                    + ev.records("install_feature_spec"))
        faults = ev.records("fault_injected")
        kills = ev.records("shard_killed")
        migr = ev.records("flow_migration")
        assert installs and faults and kills and migr
        # installs precede all supervision events; every strike happened
        # in phase 1; the kill happens after at least one shard-1 fault
        # firing from the phase-2 plan; every migration follows the kill
        # (the chaos lane adds its own low-rate fault_injected records on
        # other shards — the anchors below are robust to that)
        assert max(e.seq for e in installs) < min(
            e.seq for e in strikes + faults)
        assert max(e.seq for e in strikes) <= seq0
        kill = kills[0]
        assert len(kills) == 1 and kill.shard == 1
        assert any(seq0 < e.seq < kill.seq and e.shard == 1
                   for e in faults)
        assert kill.detail["reason"]
        assert all(e.seq > kill.seq for e in migr)
        assert all(e.shard in (0, 2, 3) for e in migr)
        assert all(e.detail["source"] == 1 for e in migr)
        assert (sum(e.detail["flows"] for e in migr)
                == fab.fault_stats["fabric_migrated_flows_total"]
                == kill.detail["flows"])
        # the counters agree with the log
        assert fab.fault_stats["fabric_deaths_total"] == len(kills) == 1
        assert (fab.fault_stats["fabric_watchdog_strikes_total"]
                == len(strikes))


class TestChaosEvents:
    def test_every_chaos_fault_is_an_event(self, monkeypatch):
        """CI chaos lane: each ``plan.fired`` entry has exactly one
        ``fault_injected`` record in the server's event log."""
        monkeypatch.setenv("REPRO_CHAOS", "1")
        monkeypatch.setenv("REPRO_CHAOS_EVERY", "3")
        srv = _plain()
        plan = srv.ingress.fault_plan
        assert plan is not None
        assert plan.events is srv.obs.events
        srv.submit_raw(_trace(400, 17))
        out = srv.drain_packets()
        assert len(plan.fired) > 0
        events = srv.obs.events.records("fault_injected")
        assert len(events) == len(plan.fired)
        # chaos firings are transient (swallowed by retries): the log
        # records them even though no caller ever saw an error
        assert not any(isinstance(r, PacketError) for r in out)
        assert srv.ingress.stats["ingress_dispatch_retries_total"] > 0


class TestExport:
    def test_prometheus_round_trip(self):
        srv = _plain(trace_every=16)
        srv.submit_raw(_trace(300, 5))
        srv.drain_packets()
        text = srv.obs.to_prometheus_text()
        snap = srv.obs.registry.snapshot()
        parsed = {}
        for line in text.splitlines():
            if not line or line.startswith("#"):
                continue
            key, val = line.rsplit(" ", 1)
            parsed[key] = float(val)

        def is_hist_summary(v):
            return (isinstance(v, dict) and "count" in v and "sum" in v
                    and not any("=" in k for k in v))

        assert snap  # the instrumented server exports something
        for name, v in snap.items():
            if is_hist_summary(v):
                assert parsed[f"{name}_count"] == v["count"]
            elif isinstance(v, dict):
                for lt, lv in v.items():
                    if is_hist_summary(lv):
                        assert parsed[f"{name}_count{{{lt}}}"] == lv["count"]
                    else:
                        assert parsed[f"{name}{{{lt}}}"] == lv
            else:
                assert parsed[name] == v
        # spot checks: canonical names, per-shard labels, engine mirror
        assert parsed['ingress_packets_total{shard="0"}'] == 300
        assert parsed['engine_retraces_total{shard="0"}'] >= 0

    def test_prometheus_help_and_type_per_family(self):
        srv = _plain()
        srv.submit_raw(_trace(120, 6))
        srv.drain_packets()
        text = srv.obs.to_prometheus_text()
        helped, typed = set(), set()
        for line in text.splitlines():
            if line.startswith("# HELP "):
                helped.add(line.split(" ", 3)[2])
            elif line.startswith("# TYPE "):
                typed.add(line.split(" ", 3)[2])
        snap = srv.obs.registry.snapshot()
        fams = {n.removesuffix("_count").removesuffix("_sum")
                for n in snap}
        # every exported family leads with both comment lines
        assert helped == typed
        assert {f for f in fams if not f.endswith(("_count", "_sum"))} \
            <= helped

    def test_prometheus_label_values_escaped(self):
        reg = MetricsRegistry()
        reg.counter("odd_total", "spot", rule='q"\\x\nend').inc(3)
        text = reg.to_prometheus_text()
        line = [ln for ln in text.splitlines()
                if ln.startswith("odd_total{")][0]
        assert line == 'odd_total{rule="q\\"\\\\x\\nend"} 3'
        # the raw control characters never leak into the exposition
        assert "\n".join(text.splitlines()) == text.rstrip("\n")

    def test_snapshot_shape(self):
        srv = _plain(trace_every=32)
        srv.submit_raw(_trace(200, 9))
        srv.drain_packets()
        snap = srv.obs.snapshot()
        assert set(snap) == {"metrics", "events", "trace"}
        assert snap["trace"]["every"] == 32
        assert snap["trace"]["sampled"] > 0
        assert any(e["kind"] == "install" for e in snap["events"])
        m = snap["metrics"]
        assert m['ingress_packets_total']['shard="0"'] == 200


class TestStatsNaming:
    def test_canonical_keys_read_and_write_through(self):
        srv = _plain()
        srv.submit_raw(_trace(100, 3))
        srv.drain_packets()
        stats = srv.ingress.stats
        assert stats["ingress_packets_total"] == 100
        before = stats["ingress_cache_hits_total"]
        stats["ingress_cache_hits_total"] += 5  # the dict write pattern
        # the registry cell is the same store
        reg = srv.obs.registry.snapshot()
        assert reg["ingress_cache_hits_total"]['shard="0"'] == before + 5
        assert "lane_batches" in stats  # nested surface
        assert set(stats["lane_batches"].keys()) >= {"mlp", "forest",
                                                     "both"}

    def test_legacy_aliases_are_gone(self):
        """The PR-8 one-release legacy spellings were removed: a legacy
        key is a plain KeyError now, not a warning."""
        srv = _plain()
        srv.submit_raw(_trace(100, 3))
        srv.drain_packets()
        for adapter, legacy in ((srv.ingress.stats, "packets"),
                                (srv.ingress.stats, "cache_hits"),
                                (srv.flow.table.stats, "lookups"),
                                (srv.flow.stats, "raw_packets")):
            assert legacy not in adapter
            with pytest.raises(KeyError):
                adapter[legacy]
        both = srv.ingress.stats.as_dict()
        assert "packets" not in both
        assert both["ingress_packets_total"] == 100

    def test_flow_canonical_keys(self):
        srv = _plain()
        srv.submit_raw(_trace(100, 3))
        srv.drain_packets()
        t = srv.flow.table
        assert t.stats["flow_lookups_total"] > 0
        assert srv.flow.stats["flow_raw_packets_total"] == 100

    def test_fabric_fault_stats_canonical(self):
        fab = _fabric(2)
        fab.submit_raw(_trace(100, 3))
        fab.drain_packets()
        assert fab.kill_shard(0, "drill") is True
        fs = fab.fault_stats
        assert fs["fabric_deaths_total"] == 1
        assert fs["dead_shards"][0]["shard"] == 0
        faults = fab.stats()["faults"]
        assert faults["fabric_deaths_total"] == 1
        assert "deaths" not in faults


class TestStatsNeverBlocks:
    def test_stats_completes_while_fabric_lock_is_held(self):
        """Regression (PR-8 satellite): ``stats()`` used to recompute
        under the fabric lock, so an operator poll stalled behind any
        in-flight ``submit_raw``.  It now snapshots registry cells
        lock-free."""
        fab = _fabric(2)
        fab.submit_raw(_trace(100, 3))
        fab.drain_packets()
        got = {}

        def poll():
            got["stats"] = fab.stats()

        with fab._lock:  # simulate a long submit holding THE fence
            th = threading.Thread(target=poll)
            th.start()
            th.join(5.0)
            alive = th.is_alive()
        assert not alive, "stats() blocked on the fabric lock"
        assert got["stats"]["n_shards"] == 2
        assert got["stats"]["faults"]["fabric_deaths_total"] == 0

    def test_stats_consistent_with_locked_view(self):
        fab = _fabric(2)
        fab.submit_raw(_trace(150, 8))
        fab.drain_packets()
        st_ = fab.stats()
        assert st_["flows"] == sum(len(sh._flow.table) for sh in fab.shards
                                   if sh._flow is not None)
        assert st_["alive_shards"] == [0, 1]
        assert sum(d["packets"] for d in st_["shards"]) == 150


class TestObservabilityBundle:
    def test_shared_registry_across_shards(self):
        fab = _fabric(2, trace_every=8)
        fab.submit_raw(_trace(200, 4))
        fab.drain_packets()
        snap = fab.obs.registry.snapshot()
        pk_cells = snap["ingress_packets_total"]
        assert set(pk_cells) == {'shard="0"', 'shard="1"'}
        assert sum(pk_cells.values()) == 200
        # per-shard tracers share one bundle; merged spans sort by submit
        spans = fab.obs.spans()
        subs = [s["submit"] for s in spans]
        assert subs == sorted(subs)
        assert {s["shard"] for s in spans} <= {0, 1}

    def test_gate_events_reach_the_log(self):
        reg_events = []
        obs = Observability()
        log = obs.events
        log.emit("gate_closed", shard=0, generation=3, dup_ewma=0.1)
        log.emit("gate_open", shard=0, generation=3, dup_ewma=0.4)
        assert [e.kind for e in log.records()] == ["gate_closed",
                                                   "gate_open"]
        assert not reg_events  # silence the linter about the placeholder

    def test_registry_attach_and_collector(self):
        reg = MetricsRegistry()
        adapter = StatsAdapter()
        from repro.obs import Counter
        c = adapter.bind("demo_things_total", Counter())
        adapter["demo_things_total"] += 3
        reg.attach("demo_things_total", c, shard=7)
        seen = []
        reg.register_collector(lambda: seen.append(True))
        snap = reg.snapshot()
        assert snap["demo_things_total"]['shard="7"'] == 3
        assert seen  # collectors run at export
