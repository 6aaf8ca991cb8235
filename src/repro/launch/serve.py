"""The packet server — the paper's system: the in-network data plane
processing encapsulated feature packets against control-plane tables
(µs-scale inference, weight hot-swap without recompile).

:class:`PacketServer` serves through the **ingress pipeline**
(``core/ingress.py``): ragged per-connection chunks are coalesced into
fixed-shape mixed-model batches (zero retraces), byte-identical duplicate
packets short-circuit through a generation-aware result cache (invalidated
automatically by ``install()``/``remove()``), and host staging is
double-buffered so packing batch N+1 overlaps device compute of batch N.
``install()`` during serving is safe and retrace-free: the control plane
publishes a new table generation while in-flight batches keep the old
buffers (double buffering).

The LM decode server lives in :mod:`repro.launch.lm_serve`, so importing
this module loads no LM model code.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

import numpy as np

from ..core.control_plane import ControlPlane
from ..core.inference import DataPlaneEngine
from ..core.ingress import IngressPipeline
from ..serve import ShardedPacketServer

__all__ = ["PacketServer", "ShardedPacketServer"]


class PacketServer:
    """Deployment wrapper: ControlPlane + DataPlaneEngine + ingress pipeline
    (+ the stateful flow engine, created on first use).

    Two serving surfaces:

      * **raw-packet API** — ``submit_raw()`` accepts raw 5-tuple header
        batches (no feature block): the flow engine (``repro.flow``)
        resolves each packet's flow, updates its registers (counters,
        EWMAs, count-min sketch) and builds each model's input columns from
        its installed :class:`FeatureSpec` before handing the encapsulated
        rows to the stream path below — serving starts where the hardware
        does.
      * **stream API** — ``submit_packets()`` accepts ragged per-connection
        chunks; ``drain_packets()`` returns per-packet egress rows (or
        per-packet error slots) in exact submission order.  This is the
        paper-shaped path: coalescing queue → duplicate cache → fused
        kernel → deparse.  With tree ensembles installed
        (:meth:`install_forest`), the queue stages MLP- and forest-family
        packets into lane-pure device batches, so mixed-family traffic pays
        each packet's own compute lane only.

    :meth:`process` is the blocking wire-path reference: one batch through
    the engine, with no pipeline, cache or flow state.
    """

    def __init__(self, *, max_models: int = 16, max_layers: int = 4,
                 max_width: int = 32, frac_bits: int = 8,
                 weight_bits: int = 16, taylor_order: int = 3,
                 kernel_variant: str = "int16",
                 forest_variant: str = "auto",
                 max_inflight: int = 8, ingress_batch: int = 2048,
                 use_cache: bool = True, cache_capacity_pow2: int = 16,
                 max_forests: int = 8, max_trees: int = 16,
                 max_nodes: int = 64, max_tree_depth: int = 6,
                 flush_after: Optional[float] = None,
                 adaptive_batch: bool = False,
                 flow_capacity_pow2: int = 14,
                 flow_idle_timeout: Optional[int] = None,
                 strict_model_ids: bool = False,
                 queue_capacity: Optional[int] = None,
                 queue_high_watermark: Optional[int] = None,
                 max_retries: int = 2, retry_backoff: float = 0.0,
                 clock=None, obs=None, trace_every: int = 0,
                 drift_window: int = 0, drift_lanes: int = 8,
                 psi_threshold: float = 0.25,
                 shadow_model: Optional[int] = None, shadow_every: int = 8,
                 slo_budget: Optional[float] = None):
        if max_inflight < 1:
            raise ValueError("max_inflight must be >= 1")
        if obs is None:
            from ..obs import Observability
            obs = Observability(clock=clock, trace_every=trace_every)
        self.obs = obs
        self.control_plane = ControlPlane(
            max_models=max_models, max_layers=max_layers,
            max_width=max_width, weight_bits=weight_bits,
            frac_bits=frac_bits, max_forests=max_forests,
            max_trees=max_trees, max_nodes=max_nodes,
            max_tree_depth=max_tree_depth)
        self.engine = DataPlaneEngine(self.control_plane,
                                      max_features=max_width,
                                      taylor_order=taylor_order,
                                      kernel_variant=kernel_variant,
                                      forest_variant=forest_variant)
        # the pipeline pools max_inflight+2 staging buffers of
        # ingress_batch feature rows each (two open family batches + the
        # in-flight window)
        self.ingress = IngressPipeline(
            self.engine, batch_size=ingress_batch,
            max_inflight=max_inflight, use_cache=use_cache,
            cache_capacity_pow2=cache_capacity_pow2,
            flush_after=flush_after, adaptive_batch=adaptive_batch,
            max_retries=max_retries, retry_backoff=retry_backoff,
            clock=clock, queue_capacity=queue_capacity,
            queue_high_watermark=queue_high_watermark, obs=obs)
        self.control_plane.events = obs.events
        # -- model-quality plane (PR 9): drift taps + shadow lane + SLO ----
        self._submit_h = None
        if drift_window or shadow_model is not None or slo_budget is not None:
            mon = obs.enable_drift(
                window=drift_window or 4096, n_lanes=drift_lanes,
                psi_threshold=psi_threshold)
            # freeze the drift reference window at every committed install
            self.control_plane.install_listeners.append(mon.on_install)
            if shadow_model is not None:
                mon.attach_shadow(self.ingress, shadow_model,
                                  every=shadow_every)
            if slo_budget is not None:
                if slo_budget <= 0:
                    raise ValueError("slo_budget must be positive (or None)")
                h = obs.registry.histogram("server_submit_seconds")
                self._submit_h = h

                def _burn() -> float:
                    return (h.percentile(99.0) / slo_budget
                            if h.count else float("nan"))

                obs.health.add_rule("slo:submit_p99", "slo_burn", _burn,
                                    1.0, budget_s=slo_budget)
        self.strict_model_ids = strict_model_ids
        # flow engine (stage 0): created on first submit_raw() so pure
        # feature-vector deployments never allocate the register file
        self._flow_capacity_pow2 = flow_capacity_pow2
        self._flow_idle_timeout = flow_idle_timeout
        self._flow: Optional["FlowFrontend"] = None

    def install(self, model_id: int, layers, activations, **kw) -> int:
        """Quantize + install (hot-swap) a model — safe mid-serving: the new
        table generation applies from the next submitted batch, zero
        retraces, in-flight batches unaffected.  The result cache keys on
        the table generation, so the bumped counter instantly orphans every
        cached egress row computed under the old weights."""
        return self.control_plane.install(model_id, layers, activations, **kw)

    def install_forest(self, model_id: int, forest) -> int:
        """Quantize + install (hot-swap) a tree ensemble
        (:class:`repro.forest.Forest` or ``PackedForest``) — same
        mid-serving safety and cache-invalidation contract as
        :meth:`install`: one shared generation counter covers both table
        families."""
        return self.control_plane.install_forest(model_id, forest)

    def warm(self) -> None:
        """Compile every serving program the pipeline will dispatch (see
        :meth:`IngressPipeline.compile_programs`): a kernel that does not
        lower raises here, before any traffic is taken."""
        self.ingress.compile_programs()

    def remove(self, model_id: int) -> None:
        """Uninstall a model and drop its cached egress rows."""
        self.control_plane.remove(model_id)
        self.ingress.on_model_removed(model_id)

    def process(self, packets):
        """Synchronous single-batch path (blocks until egress is ready)."""
        return self.engine.process(packets)

    # -- raw-packet ingress (stateful flow engine, stage 0) ----------------

    @property
    def flow(self) -> "FlowFrontend":
        """The stateful flow engine (:class:`repro.flow.FlowFrontend`),
        created lazily on first use."""
        if self._flow is None:
            from ..flow import FlowFrontend
            self._flow = FlowFrontend(
                self.ingress, capacity_pow2=self._flow_capacity_pow2,
                idle_timeout=self._flow_idle_timeout)
            # graft the flow engine's standalone counters into the shared
            # registry, plus a live occupancy gauge
            reg = self.obs.registry
            flow = self._flow
            for name, cell in flow.table.stats.cells():
                reg.attach(name, cell)
            for name, cell in flow.stats.cells():
                reg.attach(name, cell)
            g_occ = reg.gauge("flow_occupancy")
            reg.register_collector(lambda: g_occ.set(len(flow.table)))
        return self._flow

    def install_feature_spec(self, model_id: int, columns) -> int:
        """Install (hot-swap) the flow-feature → input-column mapping for a
        model (:class:`~repro.core.control_plane.FeatureSpec`).  Applies
        from the next ``submit_raw()`` batch; zero data-plane retraces."""
        return self.control_plane.install_feature_spec(model_id, columns)

    def install_slo_budget(self, model_id: int, budget_us: float) -> int:
        """Install (hot-swap) a model's per-packet hard-latency budget —
        the deadline-aware batch closer ships a short batch rather than
        let a staged packet's remaining budget drop below the measured
        dispatch cost."""
        return self.control_plane.install_slo_budget(model_id, budget_us)

    def install_reflex(self, model_id: int, program) -> int:
        """Install (hot-swap) a model's reflex fallback program
        (:class:`~repro.serve.reflex.ReflexProgram`) and attach the async
        model-lane confirmer, so ``reflex_agreement`` is measured."""
        gen = self.control_plane.install_reflex(model_id, program)
        if self.ingress.reflex_confirm is None:
            from ..serve.reflex import ReflexConfirmer
            self.ingress.reflex_confirm = ReflexConfirmer(self.ingress)
        return gen

    def remove_reflex(self, model_id: int) -> None:
        self.control_plane.remove_reflex(model_id)

    def submit_raw(self, raw) -> tuple:
        """Feed one batch of **raw 5-tuple headers**
        (``repro.data.packets.RAW_HEADER_BYTES``-byte rows — no feature
        block) through the flow engine: per-flow register update → feature
        extraction → per-model FeatureSpec gather → encapsulation → the
        ingress pipeline.  Returns ``(first_ticket, n_packets)``; results
        arrive via :meth:`drain_packets` in submission order, interleaving
        freely with :meth:`submit_packets` chunks.

        Rows that fail admission — truncated/oversized headers, a
        wrong-width batch, or (with ``strict_model_ids=True``) a Model ID
        not currently installed — never touch flow state and resolve as
        per-packet :class:`~repro.core.ingress.PacketError` slots at their
        submission-order positions (:func:`repro.data.packets.
        validate_raw_rows`); the well-formed rows in the same batch serve
        normally."""
        from ..data.packets import validate_raw_rows
        with self.obs.span("flow.parse", self.ingress.shard_id):
            known = (self.control_plane.installed_ids()
                     if self.strict_model_ids else None)
            rows, bad, reasons = validate_raw_rows(raw,
                                                   known_model_ids=known)
        t0 = time.perf_counter() if self._submit_h is not None else 0.0
        try:
            if bad is None:
                return self.flow.submit_raw(rows)
            return self.flow.submit_raw(rows, drop_mask=bad,
                                        drop_reason=reasons)
        finally:
            if self._submit_h is not None:
                self._submit_h.observe(time.perf_counter() - t0)

    # -- streaming ingress (coalescing queue + duplicate cache) ------------

    def submit_packets(self, packets) -> tuple:
        """Feed one ragged per-connection chunk into the ingress pipeline.
        Returns ``(first_ticket, n_packets)``; results arrive in submission
        order via :meth:`drain_packets`."""
        if self._submit_h is None:
            return self.ingress.submit(packets)
        t0 = time.perf_counter()
        try:
            return self.ingress.submit(packets)
        finally:
            self._submit_h.observe(time.perf_counter() - t0)

    def drain_packets(self, timeout_us: Optional[float] = None) -> list:
        """Flush the pipeline and return one entry per submitted packet in
        submission order: an egress row (``np.ndarray``) or a
        :class:`~repro.core.ingress.PacketError` slot.  ``timeout_us``
        bounds the drain — unresolved tickets backfill as
        ``PacketError(DRAIN_TIMEOUT)`` instead of blocking on a wedged
        device."""
        out = self.ingress.drain(timeout_us)
        if self.obs.health is not None:
            # step alert rules once per drain window (drift rules also
            # step on the monitor's own window cadence)
            self.obs.health.evaluate()
        return out

    def stats(self) -> Dict[str, float]:
        out = {"recompiles": self.engine.trace_count,
               "table_generation": self.control_plane.version,
               "cache_hit_rate": self.ingress.cache_hit_rate(),
               "cache_entries": (len(self.ingress.cache)
                                 if self.ingress.cache is not None else 0)}
        if self._flow is not None:
            out["flow_table_hit_rate"] = self._flow.flow_table_hit_rate()
            out["flows"] = len(self._flow.table)
        return out


def main(argv=None) -> int:
    """``python -m repro.launch.serve`` — drive a synthetic raw-header trace
    through a (possibly sharded) server and export the telemetry snapshot.

    The point is operational: CI runs this with ``--metrics-json`` to
    archive a metrics artifact per build, and
    ``--prometheus`` prints the text-exposition form for eyeballing.
    Exits 1 when any packet resolved to an error slot."""
    import argparse
    import json

    p = argparse.ArgumentParser(
        prog="repro.launch.serve",
        description="serve a synthetic raw trace; export telemetry")
    p.add_argument("--packets", type=int, default=4096,
                   help="total raw packets to serve (default 4096)")
    p.add_argument("--shards", type=int, default=1,
                   help="1 = PacketServer, >1 = ShardedPacketServer")
    p.add_argument("--flows", type=int, default=64,
                   help="synthetic flow count (default 64)")
    p.add_argument("--chunk", type=int, default=512,
                   help="submit chunk size (default 512)")
    p.add_argument("--trace-every", type=int, default=0,
                   help="sample 1-in-N packet lifecycles (0 = off)")
    p.add_argument("--drift-window", type=int, default=0,
                   help="enable the drift monitor with this window size "
                        "(feature rows per model; 0 = off)")
    p.add_argument("--shadow-model", type=int, default=None,
                   help="shadow-score a deterministic packet sample "
                        "against this Model ID (installs a copy of the "
                        "primary under that id)")
    p.add_argument("--metrics-json", metavar="PATH", default=None,
                   help="write the observability snapshot as JSON")
    p.add_argument("--prometheus", action="store_true",
                   help="print the Prometheus text exposition to stdout")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    from ..data.packets import raw_trace
    from .compile_cache import enable as enable_compile_cache

    enable_compile_cache()
    width = 16
    kw: Dict[str, Any] = dict(
        max_models=4, max_width=width, ingress_batch=256, max_inflight=2,
        flow_capacity_pow2=12, trace_every=args.trace_every,
        drift_window=args.drift_window, shadow_model=args.shadow_model)
    if args.shards > 1:
        srv: Any = ShardedPacketServer(n_shards=args.shards, **kw)
    else:
        srv = PacketServer(**kw)
    rng = np.random.default_rng(args.seed)
    r = np.random.default_rng(args.seed + 1)
    w1 = r.normal(size=(width, width)).astype(np.float32) * 0.3
    w2 = r.normal(size=(width, 4)).astype(np.float32) * 0.3
    layers = [(w1, np.zeros(width, np.float32)),
              (w2, np.zeros(4, np.float32))]
    srv.install(1, layers, ["relu"], final_activation="sigmoid")
    srv.install_feature_spec(1, (2, 3, 4, 5) * (width // 4))
    if args.shadow_model is not None:
        # identical copy — the shadow lane should report full agreement
        srv.install(args.shadow_model, layers, ["relu"],
                    final_activation="sigmoid")

    raw = raw_trace(rng, args.packets, n_flows=args.flows,
                    model_ids=(1,), pattern="mixed")
    t0 = time.perf_counter()
    for i in range(0, raw.shape[0], args.chunk):
        srv.submit_raw(raw[i: i + args.chunk])
    out = srv.drain_packets()
    dt = time.perf_counter() - t0
    n_err = sum(1 for o in out if not isinstance(o, np.ndarray))

    snap = srv.obs.snapshot()
    snap["run"] = {"packets": int(raw.shape[0]), "errors": int(n_err),
                   "seconds": dt, "packets_per_s": raw.shape[0] / dt,
                   "shards": args.shards}
    if args.metrics_json:
        with open(args.metrics_json, "w") as f:
            json.dump(snap, f, indent=2, sort_keys=True, default=str)
    if args.prometheus:
        print(srv.obs.to_prometheus_text(), end="")
    print(f"served {raw.shape[0]} packets on {args.shards} shard(s) in "
          f"{dt * 1e3:.1f} ms ({raw.shape[0] / dt:,.0f} pkt/s), "
          f"{n_err} error slots"
          + (f"; metrics -> {args.metrics_json}"
             if args.metrics_json else ""))
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
