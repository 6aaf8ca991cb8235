"""Hard-latency serving tests (PR 10: deadline-aware scheduler, admission
backpressure, reflex fallback lane, bounded drain, overload chaos).

  * per-model SLO budgets and reflex programs are control-plane table
    families: prepare-then-commit installs, crash-safe under the install
    fault site, hot-swappable, one generation counter
  * the packed reflex evaluation matches the scalar ``reflex_oracle``
    element for element (hypothesis, random programs and inputs)
  * the watermark controller allocates queue space in exact submission
    order: below the high watermark packets stage, past it they answer on
    the reflex lane, past hard capacity they shed as typed
    ``PacketError(DEADLINE_SHED)`` slots — and the model-lane slots are
    bit-exact with an unconstrained N=1 oracle
  * deadline-aware batch closing is exact on the injectable clock: a
    packet at budget-minus-epsilon ships a short batch, at
    budget-plus-epsilon waits, and deadline-closed short batches reuse
    the ladder's jit shapes (zero retraces)
  * ``drain(timeout_us=)`` / ``drain_packets(timeout_us=)`` always
    return: a wedged shard overshoots by at most its one stuck step and
    its unresolved tickets come back as ``PacketError(DRAIN_TIMEOUT)``
  * the ``"overload"`` chaos site makes one shard's device slow for
    real: sheds stay local to that shard and survivors' submit p99 stays
    within budget
"""

import time
import types

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp

from repro.core import packet as pk
from repro.core.control_plane import ControlPlane
from repro.core.inference import DataPlaneEngine
from repro.core.ingress import (DEADLINE_SHED, DRAIN_TIMEOUT,
                                IngressPipeline, PacketError)
from repro.launch.serve import PacketServer
from repro.serve import (FaultPlan, FaultSpec, InjectedFault, ReflexProgram,
                         ShardedPacketServer, reflex_oracle)

FRAC = 8
WIDTH = 8
FOREVER = 1 << 60


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _layers(rng):
    w1 = rng.normal(size=(WIDTH, WIDTH)).astype(np.float32) * 0.3
    w2 = rng.normal(size=(WIDTH, 2)).astype(np.float32) * 0.3
    return [(w1, np.zeros(WIDTH, np.float32)), (w2, np.zeros(2, np.float32))]


def _cp(mids=(10, 11), seed=0, **cp_kw):
    cp_kw.setdefault("max_models", 16)
    cp_kw.setdefault("max_layers", 2)
    cp_kw.setdefault("max_width", WIDTH)
    cp_kw.setdefault("frac_bits", FRAC)
    cp = ControlPlane(**cp_kw)
    rng = np.random.default_rng(seed)
    for mid in mids:
        cp.install(mid, _layers(rng), ["relu"], final_activation="sigmoid")
    return cp


def _pipeline(mids=(10, 11), seed=0, **kw):
    cp = _cp(mids=mids, seed=seed)
    eng = DataPlaneEngine(cp, max_features=WIDTH)
    kw.setdefault("batch_size", 16)
    kw.setdefault("use_cache", False)
    return cp, eng, IngressPipeline(eng, **kw)


def _wire(rng, n, mid=10):
    codes = rng.integers(-2000, 2000, (n, WIDTH)).astype(np.int32)
    rows = np.asarray(pk.encode_packets(
        jnp.asarray(np.full(n, mid, np.int32)), jnp.int32(FRAC),
        jnp.asarray(codes)))
    return rows, codes


def _prog(on_true=(256, 0), on_false=(0, 256), lane=0, thr=0):
    return ReflexProgram.threshold(lane, thr, on_true=on_true,
                                   on_false=on_false)


def _install_fab(srv, mids=(1,), seed=7):
    rng = np.random.default_rng(seed)
    for mid in mids:
        srv.install(mid, _layers(rng), ["relu"],
                    final_activation="sigmoid")
        srv.install_feature_spec(mid, list(range(WIDTH)))
    return srv


def _fabric(n, mids=(1,), **kw):
    kw.setdefault("max_width", WIDTH)
    kw.setdefault("frac_bits", FRAC)
    kw.setdefault("ingress_batch", 16)
    kw.setdefault("max_inflight", 2)
    return _install_fab(ShardedPacketServer(n_shards=n, **kw), mids=mids)


def _fab_wire(rng, n, mid=1):
    return _wire(rng, n, mid=mid)[0]


# ---------------------------------------------------------------------------
# control-plane table families: SLO budgets + reflex programs
# ---------------------------------------------------------------------------


class TestControlPlaneSLO:
    def test_install_and_remove_budget(self):
        cp = _cp()
        assert not cp.slo_active
        v0 = cp.version
        cp.install_slo_budget(10, 250.0)
        assert cp.version > v0
        assert cp.slo_active
        assert cp.slo_budget(10) == pytest.approx(250.0)
        assert np.isinf(cp.slo_budget(11))
        rows = cp.slo_budget_rows(np.array([10, 11, 10], np.int32))
        assert rows[0] == pytest.approx(250.0) and np.isinf(rows[1])
        cp.remove_slo_budget(10)
        assert np.isinf(cp.slo_budget(10))
        assert cp.slo_active            # monotone: the cheap gate stays on

    def test_budget_validation(self):
        cp = _cp()
        with pytest.raises(ValueError):
            cp.install_slo_budget(10, 0.0)
        with pytest.raises(ValueError):
            cp.install_slo_budget(10, -5.0)

    def test_install_kwarg_sets_budget(self):
        cp = _cp(mids=())
        rng = np.random.default_rng(1)
        cp.install(3, _layers(rng), ["relu"], final_activation="sigmoid",
                   slo_budget_us=500.0)
        assert cp.slo_active
        assert cp.slo_budget(3) == pytest.approx(500.0)

    def test_reflex_install_round_trip(self):
        cp = _cp()
        assert not cp.reflex_active
        p = _prog()
        v0 = cp.version
        cp.install_reflex(10, p)
        assert cp.version > v0
        assert cp.reflex_active
        assert cp.reflex_program(10) == p
        mask = cp.reflex_mask(np.array([10, 11], np.int32))
        assert mask.tolist() == [True, False]
        cp.remove_reflex(10)
        assert cp.reflex_program(10) is None
        assert not cp.reflex_mask(np.array([10], np.int32))[0]
        assert cp.reflex_active         # monotone: the cheap gate stays on

    def test_reflex_install_crash_safe(self):
        cp = _cp()
        plan = FaultPlan([FaultSpec(site="install", count=1)])
        cp.fault_plan = plan
        v0 = cp.version
        with pytest.raises(InjectedFault):
            cp.install_reflex(10, _prog())
        assert cp.version == v0
        assert not cp.reflex_active
        cp.install_reflex(10, _prog())  # clean retry lands
        assert cp.reflex_active

    def test_program_validation(self):
        with pytest.raises(ValueError):
            ReflexProgram(lanes=(), thresholds=(), weights=(),
                          on_true=(1,), on_false=(0,))
        with pytest.raises(ValueError):
            ReflexProgram(lanes=(0, 1), thresholds=(5,), weights=(1, 1),
                          on_true=(1,), on_false=(0,))
        with pytest.raises(ValueError):
            ReflexProgram(lanes=(0,), thresholds=(5,), weights=(1,),
                          on_true=(1, 2), on_false=(0,))
        with pytest.raises(ValueError):
            ReflexProgram(lanes=(-1,), thresholds=(5,), weights=(1,),
                          on_true=(1,), on_false=(0,))

    @settings(max_examples=30, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=10 ** 6))
    def test_packed_evaluate_matches_oracle(self, seed):
        rng = np.random.default_rng(seed)
        k = int(rng.integers(1, 5))
        prog = ReflexProgram(
            lanes=tuple(rng.integers(0, WIDTH, k).tolist()),
            thresholds=tuple(rng.integers(-2000, 2001, k).tolist()),
            weights=tuple(rng.integers(-3, 4, k).tolist()),
            bias=int(rng.integers(-3, 4)),
            on_true=tuple(rng.integers(-500, 501, 2).tolist()),
            on_false=tuple(rng.integers(-500, 501, 2).tolist()))
        cp = _cp(mids=())
        cp.install_reflex(5, prog)
        x = rng.integers(-2500, 2500, (12, WIDTH)).astype(np.int32)
        mids = np.full(12, 5, np.int32)
        _, out = cp.reflex_evaluate(mids, x)
        for i in range(12):
            assert out[i, :prog.out_dim].tolist() == reflex_oracle(
                prog, x[i])


# ---------------------------------------------------------------------------
# watermark admission: stage / reflex / shed in exact submission order
# ---------------------------------------------------------------------------


class TestWatermarkAdmission:
    def test_reflex_past_high_watermark_in_submission_order(self):
        cp, eng, pipe = _pipeline(queue_capacity=64,
                                  queue_high_watermark=16)
        prog = _prog()
        cp.install_reflex(10, prog)
        rng = np.random.default_rng(3)
        wire, codes = _wire(rng, 80)
        pipe.submit(wire)
        out = pipe.drain()
        assert len(out) == 80
        reflexed = [i for i, r in enumerate(out)
                    if not isinstance(r, PacketError)
                    and (int(r[6]) & pk.FLAG_REFLEX)]
        assert reflexed == list(range(16, 80))
        assert pipe.stats["ingress_reflex_served_total"] == 64
        # reflex answers are bit-exact with the scalar oracle
        for i in reflexed:
            want = np.zeros(pipe.out_feats, np.int32)
            want[:prog.out_dim] = reflex_oracle(prog, codes[i])
            row = pk.emit_results_np(
                np.array([10], np.int32), np.array([int(out[i][6])]),
                want[None], eng.frac)[0]
            assert np.array_equal(out[i], row)
        ev = [e for e in pipe.obs.events.records(kind="reflex_served")]
        assert ev and sum(e.detail["count"] for e in ev) == 64

    def test_shed_past_hard_capacity_in_submission_order(self):
        cp, eng, pipe = _pipeline(queue_capacity=32)
        rng = np.random.default_rng(3)
        wire, _ = _wire(rng, 80, mid=11)   # no reflex program installed
        pipe.submit(wire)
        out = pipe.drain()
        shed = [i for i, r in enumerate(out) if isinstance(r, PacketError)]
        assert shed == list(range(32, 80))
        assert all(out[i].reason == DEADLINE_SHED for i in shed)
        assert pipe.stats["ingress_shed_total"] == 48
        ev = pipe.obs.events.records(kind="deadline_shed")
        assert ev and sum(e.detail["count"] for e in ev) == 48

    def test_model_lane_slots_match_unconstrained_oracle(self):
        rng = np.random.default_rng(3)
        wire, _ = _wire(rng, 80, mid=11)
        _, _, oracle = _pipeline()
        oracle.submit(wire)
        want = oracle.drain()
        cp, _, pipe = _pipeline(queue_capacity=32)
        pipe.submit(wire)
        got = pipe.drain()
        for i in range(32):                 # staged slots: bit-exact vs N=1
            assert np.array_equal(got[i], want[i])
        for i in range(32, 80):
            assert isinstance(got[i], PacketError)

    def test_duplicates_follow_their_uniques_action(self):
        cp, eng, pipe = _pipeline(queue_capacity=8)
        rng = np.random.default_rng(5)
        wire, _ = _wire(rng, 12, mid=11)
        dup = np.vstack([wire, wire[:4]])   # 4 trailing duplicates
        pipe.submit(dup)
        out = pipe.drain()
        # uniques 0..7 stage; 8..11 shed; duplicates of 0..3 coalesce onto
        # their staged unique and resolve as results, not errors
        for i in range(8):
            assert not isinstance(out[i], PacketError)
        for i in range(8, 12):
            assert isinstance(out[i], PacketError)
        for i in range(12, 16):
            assert not isinstance(out[i], PacketError)
            assert np.array_equal(out[i], out[i - 12])

    def test_depth_reaps_completed_futures(self):
        cp, eng, pipe = _pipeline(queue_capacity=64)
        rng = np.random.default_rng(9)
        wire, _ = _wire(rng, 16, mid=11)
        pipe.submit(wire)                   # full batch: dispatched
        pipe.drain()
        assert pipe.queue_depth() == 0


# ---------------------------------------------------------------------------
# deadline-aware batch closing (injectable clock, exact at the boundary)
# ---------------------------------------------------------------------------


class TestDeadlineClosing:
    def _deadline_pipe(self):
        clk = FakeClock()
        cp, eng, pipe = _pipeline(clock=clk)
        cp.install_slo_budget(10, 500.0)
        pipe.dispatch_cost_ewma = 100e-6
        return clk, cp, eng, pipe

    def test_boundary_minus_epsilon_ships_plus_epsilon_waits(self):
        clk, cp, eng, pipe = self._deadline_pipe()
        rng = np.random.default_rng(1)
        wire, _ = _wire(rng, 4)            # partial batch, deadline t+500us
        pipe.submit(wire)
        clk.t = 399e-6                     # remaining 101us > 100us cost
        assert pipe.poll() is False
        assert pipe._open                  # still staged
        clk.t = 400e-6                     # remaining == cost: ship now
        assert pipe.poll() is True
        assert not pipe._open
        out = pipe.drain()
        assert len(out) == 4
        assert not any(isinstance(r, PacketError) for r in out)

    def test_models_without_budget_never_deadline_close(self):
        clk, cp, eng, pipe = self._deadline_pipe()
        rng = np.random.default_rng(1)
        wire, _ = _wire(rng, 4, mid=11)    # model 11 has no budget
        pipe.submit(wire)
        clk.t = 10.0
        assert pipe.poll() is False
        assert pipe._open

    def test_deadline_close_is_zero_retrace(self):
        clk, cp, eng, pipe = self._deadline_pipe()
        rng = np.random.default_rng(1)
        wire, _ = _wire(rng, 3)
        pipe.submit(wire)                  # warm the padded rung once
        pipe.drain()
        traces = eng.trace_count
        for fill in (1, 5, 9):
            w, _ = _wire(rng, fill)
            pipe.submit(w)
            clk.t += 1.0                   # way past every deadline
            assert pipe.poll() is True
            pipe.drain()
        assert eng.trace_count == traces

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=10 ** 6))
    def test_no_open_batch_ever_past_its_ship_by_point(self, seed):
        ev_rng = np.random.default_rng(seed)
        n_events = int(ev_rng.integers(1, 26))
        events = [(int(ev_rng.integers(1, 11)),
                   int(ev_rng.choice([10, 11])))
                  for _ in range(n_events)]
        clk = FakeClock()
        cp, eng, pipe = _pipeline(clock=clk)
        cp.install_slo_budget(10, 500.0)
        cp.install_slo_budget(11, 300.0)
        pipe.dispatch_cost_ewma = 100e-6
        pipe._COST_ALPHA = 0.0             # pin the cost on the fake clock
        rng = np.random.default_rng(0)
        w, _ = _wire(rng, 3)               # warm the padded rung once
        pipe.submit(w)
        clk.advance(1.0)
        pipe.poll()
        pipe.drain()
        traces = eng.trace_count
        n = 0
        for gap_ticks, mid in events:
            clk.advance(gap_ticks * 10e-6)
            w, _ = _wire(rng, 1, mid=mid)
            pipe.submit(w)
            n += 1
            pipe.poll()
            # the scheduler never leaves a batch open past its ship-by
            # time: remaining budget stays above the measured cost
            for o in pipe._open.values():
                assert o.deadline - clk.t > pipe.dispatch_cost_ewma
        out = pipe.drain()
        assert len(out) == n
        assert not any(isinstance(r, PacketError) for r in out)
        assert eng.trace_count == traces   # short closes reuse jit shapes


# ---------------------------------------------------------------------------
# bounded drain
# ---------------------------------------------------------------------------


class TestBoundedDrain:
    def test_wedged_pipeline_drain_returns_with_typed_slots(self):
        cp, eng, pipe = _pipeline()
        pipe.fault_plan = FaultPlan(
            [FaultSpec(site="stall", latency=0.25, count=1)])
        rng = np.random.default_rng(2)
        wire, _ = _wire(rng, 4)
        pipe.submit(wire)                  # partial: dispatch waits for
        out = pipe.drain(timeout_us=1000.0)  # the drain, where it stalls
        assert len(out) == 4
        assert all(isinstance(r, PacketError)
                   and r.reason == DRAIN_TIMEOUT for r in out)
        assert pipe.stats["ingress_drain_timeouts_total"] == 1
        assert pipe.obs.events.records(kind="drain_timeout")
        # the pipeline is not poisoned: the next window serves normally
        pipe.submit(wire)
        out2 = pipe.drain()
        assert not any(isinstance(r, PacketError) for r in out2)

    def test_unbounded_drain_still_blocks_through_the_stall(self):
        cp, eng, pipe = _pipeline()
        pipe.fault_plan = FaultPlan(
            [FaultSpec(site="stall", latency=0.05, count=1)])
        rng = np.random.default_rng(2)
        wire, _ = _wire(rng, 4)
        pipe.submit(wire)
        out = pipe.drain()                 # no timeout: waits it out
        assert not any(isinstance(r, PacketError) for r in out)

    def test_fabric_drain_bounds_a_wedged_shard(self):
        fab = _fabric(2)
        FaultPlan([FaultSpec(site="stall", shard=0, latency=0.3,
                             count=1)]).install(fab)
        rng = np.random.default_rng(4)
        fab.submit_packets(_fab_wire(rng, 8))    # shard 0: partial batch
        fab.submit_packets(_fab_wire(rng, 16))   # shard 1: full batch
        fab.shards[1].pipeline.flush()           # shard 1 fully retired
        out = fab.drain_packets(timeout_us=50_000.0)
        assert len(out) == 24
        for i in range(8):                 # wedged shard: typed backfill
            assert isinstance(out[i], PacketError)
            assert out[i].reason == DRAIN_TIMEOUT
        for i in range(8, 24):             # survivor still answers
            assert not isinstance(out[i], PacketError)
        p0 = fab.shards[0].pipeline
        assert p0.stats["ingress_drain_timeouts_total"] == 1
        assert p0.obs.events.records(kind="drain_timeout")


# ---------------------------------------------------------------------------
# overload chaos: shard-local shed, survivors stay fast
# ---------------------------------------------------------------------------


class TestOverloadChaos:
    def test_overload_spec_validation(self):
        with pytest.raises(ValueError):
            FaultSpec(site="overload", slowdown=0.0)
        plan = FaultPlan([FaultSpec(site="overload", shard=1,
                                    slowdown=4.0, count=FOREVER)])
        assert plan.has_site("overload")
        assert plan.overload_factor(1) == 4.0
        assert plan.overload_factor(0) == 1.0

    def test_shed_stays_local_to_the_overloaded_shard(self):
        fab = _fabric(2, queue_capacity=40)
        rng = np.random.default_rng(3)
        for _ in range(4):                 # warm both shards, seed EWMAs
            fab.submit_packets(_fab_wire(rng, 16))
        fab.drain_packets()
        for sh in fab.shards:              # pin the measured cost
            sh.pipeline.dispatch_cost_ewma = 2e-3
        FaultPlan([FaultSpec(site="overload", shard=0, slowdown=50.0,
                             count=FOREVER)]).install(fab)
        for _ in range(12):                # burst: chunks round-robin
            fab.submit_packets(_fab_wire(rng, 16))
        shed_per = [sh.pipeline.stats["ingress_shed_total"]
                    for sh in fab.shards]
        assert shed_per[0] > 0             # the slow shard sheds
        assert shed_per[1] == 0            # the survivor never does
        out = fab.drain_packets(timeout_us=5e6)
        assert len(out) == 12 * 16         # every ticket resolves
        shed = [i for i, r in enumerate(out)
                if isinstance(r, PacketError)]
        assert len(shed) == shed_per[0]
        assert all(out[i].reason == DEADLINE_SHED for i in shed)
        # shed slots all belong to shard-0 chunks (even burst chunks)
        assert all((i // 16) % 2 == 0 for i in shed)

    def test_survivor_submit_p99_stays_within_budget(self):
        fab = _fabric(2)
        rng = np.random.default_rng(11)
        from repro.data.packets import raw_trace
        for _ in range(2):                 # warm both shards
            fab.submit_raw(raw_trace(rng, 64, n_flows=32, model_ids=(1,)))
        fab.drain_packets()
        for sh in fab.shards:
            sh.pipeline.dispatch_cost_ewma = 2e-3
        # measure the drill alone: the warm window holds the one-time jit
        # compile, which is not the overload under test
        fab._submit_hist = [type(h)() for h in fab._submit_hist]
        FaultPlan([FaultSpec(site="overload", shard=0, slowdown=50.0,
                             count=FOREVER)]).install(fab)
        for _ in range(6):
            fab.submit_raw(raw_trace(rng, 64, n_flows=32, model_ids=(1,)))
        fab.drain_packets(timeout_us=10e6)
        p99 = [h.percentile(99.0) for h in fab._submit_hist]
        assert p99[1] < 0.05               # survivor within a 50ms budget
        assert p99[0] > p99[1]             # the overloaded shard is not

    def test_burst_overload_drill(self, monkeypatch):
        """One pipeline under overload: a per-model budget, a reflex
        program on model 1 (15/16 of the traffic), none on model 2, and
        the overload site holding every retire 10x the device cost.  Past
        the high watermark model 1 answers on the reflex lane, model 2
        sheds past hard capacity, and every slot resolves in submission
        order to a bit-exact model-lane row, a bit-exact reflex row or a
        typed shed.  Every answered packet meets the budget and nothing
        retraces.  Time is the injected clock: a held retire advances it
        instead of sleeping, arrivals 0.2 ms apart."""
        import repro.core.ingress as ingress
        budget_us, total, chunk, gap = 100_000.0, 4096, 64, 2e-4
        rng = np.random.default_rng(2)
        cp = _cp(mids=(), max_models=4)
        for mid in (1, 2):
            cp.install(mid, _layers(rng), ["relu"],
                       final_activation="sigmoid", slo_budget_us=budget_us)
        eng = DataPlaneEngine(cp, max_features=WIDTH)
        mids = np.where(np.arange(total) % 16 == 15, 2, 1).astype(np.int32)
        codes = rng.integers(-2000, 2000, (total, WIDTH)).astype(np.int32)
        wire = np.asarray(pk.encode_packets(
            jnp.asarray(mids), jnp.int32(FRAC), jnp.asarray(codes)))
        chunks = [wire[i:i + chunk] for i in range(0, total, chunk)]
        base = IngressPipeline(eng, batch_size=256, max_inflight=4,
                               use_cache=False)
        for ch in chunks:
            base.submit(ch)
        oracle = base.drain()               # unconstrained model lane

        cp.install_reflex(1, _prog(on_true=(256, 0, 0, 0),
                                   on_false=(0, 256, 0, 0)))
        clk = FakeClock()
        monkeypatch.setattr(ingress, "time", types.SimpleNamespace(
            sleep=clk.advance, perf_counter=time.perf_counter))
        pipe = IngressPipeline(eng, batch_size=256, max_inflight=4,
                               use_cache=False, queue_capacity=320,
                               queue_high_watermark=192, clock=clk)
        for ch in chunks:                   # no-fault warm: every rung
            pipe.submit(ch)
            pipe.poll()
        pipe.drain()
        pipe.dispatch_cost_ewma = 1.2e-3    # a known device cost, pinned
        pipe._COST_ALPHA = 0.0
        pipe.fault_plan = FaultPlan([FaultSpec(
            site="overload", slowdown=10.0, count=FOREVER)], seed=3)
        traces = eng.trace_count
        pipe.reset_tickets()
        sub = np.empty(total)
        rdy = np.full(total, np.nan)
        for ch in chunks:
            sub[pipe._n_tickets: pipe._n_tickets + len(ch)] = clk()
            pipe.submit(ch)
            pipe.poll()
            pipe._resolve_ready_chunks()
            k = pipe._n_tickets
            done = np.isnan(rdy[:k]) & (pipe._status[:k] == 1)
            rdy[:k][done] = clk()
            clk.advance(gap)
        out = pipe.drain()
        rdy[np.isnan(rdy)] = clk()          # resolved during the drain
        assert eng.trace_count == traces

        assert len(out) == total
        shed = [i for i, r in enumerate(out) if isinstance(r, PacketError)]
        served = [i for i, r in enumerate(out)
                  if not isinstance(r, PacketError)]
        reflex = [i for i in served if int(out[i][6]) & pk.FLAG_REFLEX]
        model = [i for i in served if not int(out[i][6]) & pk.FLAG_REFLEX]
        assert shed and reflex and model    # all three outcomes happen
        assert all(out[i].reason == DEADLINE_SHED for i in shed)
        assert set(mids[shed]) == {2}       # only the uncovered model sheds
        for i in model:
            np.testing.assert_array_equal(out[i], oracle[i])
        rs = np.asarray(reflex)
        _, outw = cp.reflex_evaluate(mids[rs], codes[rs])
        want = pk.emit_results_np(
            mids[rs], np.array([int(out[i][6]) for i in reflex]),
            outw[:, : pipe.out_feats], eng.frac)
        for j, i in enumerate(reflex):
            np.testing.assert_array_equal(out[i], want[j])
        lat = (rdy - sub)[served]
        assert np.percentile(lat, 99) * 1e6 <= budget_us


# ---------------------------------------------------------------------------
# reflex confirmation (async model-lane agreement)
# ---------------------------------------------------------------------------


class TestReflexConfirmer:
    def test_agreement_metric_over_reflex_served_burst(self):
        srv = PacketServer(max_width=WIDTH, frac_bits=FRAC,
                           ingress_batch=16, max_inflight=2,
                           queue_high_watermark=8, use_cache=False)
        rng = np.random.default_rng(7)
        srv.install(1, _layers(rng), ["relu"], final_activation="sigmoid")
        srv.install_reflex(1, _prog())
        conf = srv.ingress.reflex_confirm
        assert conf is not None
        wire, _ = _wire(np.random.default_rng(3), 64, mid=1)
        srv.submit_packets(wire)
        out = srv.drain_packets()
        served = srv.ingress.stats["ingress_reflex_served_total"]
        assert served == 64 - 8
        assert not any(isinstance(r, PacketError) for r in out)
        assert conf.pairs == served        # every reflex answer confirmed
        assert 0.0 <= conf.agreement() <= 1.0
        assert set(conf.by_model) == {1}
        agree, pairs = conf.by_model[1]
        assert pairs == served and 0 <= agree <= pairs

    def test_confirmation_is_credit_neutral(self):
        srv = PacketServer(max_width=WIDTH, frac_bits=FRAC,
                           ingress_batch=16, max_inflight=2,
                           queue_high_watermark=8, use_cache=False)
        rng = np.random.default_rng(7)
        srv.install(1, _layers(rng), ["relu"], final_activation="sigmoid")
        srv.install_reflex(1, _prog())
        wire, _ = _wire(np.random.default_rng(3), 64, mid=1)
        srv.submit_packets(wire)
        srv.drain_packets()
        # engine packet accounting counts each submitted packet exactly
        # once: reflex answers credit, confirmation replays self-cancel
        assert srv.engine.stats["packets"] == 64
