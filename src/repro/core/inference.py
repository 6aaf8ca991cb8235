"""The batched multi-model data-plane engine (paper Fig 2, §2 "FPGA inference").

One jit-compiled program is the whole pipeline:

    parse header → Model-ID table lookup → fixed-point MLP forward with
    Taylor-approximated activations  ─┐
                                      ├→ deparse (outputs replace features)
    parse header → forest-slot lookup → tree-ensemble traversal
    (pointer-chase or range-table lowering) with majority/mean vote ─┘

and it serves a **mixed-model batch**: every packet in the batch may target a
different installed model — of either family.  Model IDs resolve through two
id_map tables (MLP slots and forest slots, one namespace); each packet's
egress row comes from whichever lane its ID belongs to, so MLP and forest
traffic interleave freely in one batch with no host-side partitioning.  The
forest lane (``kernels.forest_traverse``) only enters the compiled program
once a forest has ever been installed (``ControlPlane.forest_active`` is a
static, monotone switch — at most one extra trace per process, and a pure
MLP deployment compiles exactly the PR-1 program).

The lane-dispatch core lives in ``kernels.fused_serve.serve_lanes`` — one
definition shared by both serving surfaces:

  * ``process()`` — the **wire path**: uint8 packet batches, byte parse
    and egress deparse inside the program (blocking; kept as the
    byte-level reference the serving paths are tested against).
  * ``run_features()`` — the **feature path** (the cold-path tentpole):
    already-parsed int32 feature codes and Model IDs in, int32 output codes
    out — pure compute, one dispatch, no byte codec in the program.  The
    ingress pipeline parses each chunk once on the host
    (``core.packet.parse_packets_np``), serves every staged batch through
    this entry, and encodes egress rows once at retire
    (``emit_results_np``); both host codecs are byte-identical twins of the
    in-program ones, so the two surfaces are bit-exact (asserted by the
    tier-1 suite).

All arithmetic inside the program is integer (int32 accumulate, rounding
arithmetic shifts) — bit-exact with what the P4/FPGA pipeline would compute —
and every parameter is a traced argument fetched from the control plane, so
weight updates never recompile (asserted by ``trace_count``).  The control
plane double-buffers its tables: every dispatch snapshots the current
generation, so an ``install()`` racing an in-flight batch is safe (the
batch keeps the old buffers; the next batch picks up the new generation).

``run_features(..., block=False)`` dispatches without waiting for the
device — the ingress pipeline overlaps host-side staging and egress encode
with device compute.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.fused_serve import LaneConfig, serve_lanes
from ..kernels.forest_traversal import FOREST_VARIANTS
from ..kernels.ops import on_tpu
from ..obs import no_span
from .control_plane import ControlPlane, ForestTables, ModelTables
from .packet import FEATURE_BYTES, HEADER_BYTES, emit_results, parse_packets
from .taylor import scaled_constants

__all__ = ["DataPlaneEngine", "CompileError"]

_LANE_NAMES = {(True, False): "mlp", (False, True): "forest",
               (True, True): "both"}


class CompileError(RuntimeError):
    """A serving program failed to lower or compile.  It is a fault of the
    deployment, not of the device: it raises to the caller of the dispatch
    that needed the program, and the ingress retry, bisection and
    shard-kill handlers never see it."""


class DataPlaneEngine:
    """Batched mixed-model packet-inference pipeline over a :class:`ControlPlane`.

    Parameters
    ----------
    control_plane:
        Table owner.  The engine snapshots ``control_plane.tables()`` (the
        current double-buffer generation) each batch.
    max_features:
        Static parser bound (P4 header-stack depth).
    taylor_order:
        Sigmoid polynomial order (paper Table 3: 1, 3 or 5).
    backend:
        Kernel backend of both lanes: ``"auto"`` (Pallas on TPU, the
        gathered jnp lowering on CPU), ``"pallas"`` (force kernel,
        interpreted off-TPU) or ``"ref"``.
    kernel_variant:
        Weight lane of the fused MLP kernel (``kernels.KERNEL_VARIANTS``):
        ``"int16"`` (default, weights up to 16 bits) or ``"int8"`` — the
        saturating int8 weight-lane (int8×int8→int32 dot, v5e MXU native
        rate).  The int8 lane requires the control plane to quantize weights
        at ``weight_bits <= 8``; a wider format is rejected here so the
        narrowing cast can never silently truncate installed models.
    forest_variant:
        Traversal lowering of the forest lane (``kernels.FOREST_VARIANTS``
        plus ``"auto"``): ``"chase"`` is the level-bounded pointer chase
        (PR 3), ``"range"`` the pForest range-table compilation (parallel
        compares + leaf-mask AND-reduce, no serial gather chain).  Both are
        bit-exact against the same scalar oracle.  ``"auto"`` (default)
        picks the measured winner per platform: the chase on CPU (it only
        touches *visited* nodes and XLA:CPU vectorizes the short gather
        steps well), the range form on TPU (no step-serial dependency to
        stall the VPU; real-TPU measurement is a ROADMAP item).  ``"range"``
        requires the control plane's range family
        (``ControlPlane.range_available`` — ``max_nodes <= 64``).
    """

    def __init__(self, control_plane: ControlPlane, *, max_features: int = 16,
                 taylor_order: int = 3, leaky_alpha: float = 0.01,
                 backend: str = "auto",
                 kernel_variant: str = "int16",
                 forest_variant: str = "auto",
                 device=None):
        if backend not in ("auto", "pallas", "ref"):
            raise ValueError(f"unknown kernel backend: {backend!r}")
        if kernel_variant not in ("int16", "int8"):
            raise ValueError(f"unknown kernel variant: {kernel_variant!r}")
        if kernel_variant == "int8" and control_plane.fmt.total_bits > 8:
            raise ValueError(
                f"kernel_variant='int8' needs weight_bits <= 8, but the "
                f"control plane quantizes at {control_plane.fmt.total_bits} "
                "bits — construct it with ControlPlane(weight_bits=8)")
        if forest_variant not in FOREST_VARIANTS + ("auto",):
            raise ValueError(f"unknown forest variant: {forest_variant!r}")
        if forest_variant == "auto":
            forest_variant = "range" if (on_tpu()
                                         and control_plane.range_available) \
                else "chase"
        if forest_variant == "range" and not control_plane.range_available:
            raise ValueError(
                "forest_variant='range' needs the control plane's range "
                f"family (max_nodes={control_plane.max_nodes} > 64 exceeds "
                "the 32-leaf mask bound)")
        self.kernel_variant = kernel_variant
        self.forest_variant = forest_variant
        self.cp = control_plane
        # shard placement: with a device, every batch's operands (inputs and
        # the control plane's per-device table snapshot) are committed there,
        # so the whole dispatch runs on that device — N engines over one
        # control plane each compute on their own mesh device.  None keeps
        # the single-device behavior exactly (uncommitted default placement).
        self.device = device
        self.max_features = max_features
        # static unroll bound of the forest traversal lane (a synthesis-time
        # property of the data plane, like max_layers for the MLP lane)
        self.max_tree_depth = control_plane.max_tree_depth
        self.taylor_order = taylor_order
        self.backend = backend
        self.frac = control_plane.frac_bits
        self._leaky_alpha_q = int(round(leaky_alpha * (1 << self.frac)))
        self._sig_coeffs = tuple(
            int(c) for c in scaled_constants("sigmoid", taylor_order, self.frac))
        self.lane_cfg = LaneConfig(
            frac=self.frac, sig_coeffs=self._sig_coeffs,
            leaky_alpha_q=self._leaky_alpha_q, max_features=max_features,
            max_tree_depth=self.max_tree_depth, backend=backend,
            kernel_variant=kernel_variant, forest_variant=forest_variant)
        self.out_features = min(max_features, int(control_plane.max_width))
        self.trace_count = 0
        self.stats = {"packets": 0, "bytes_in": 0, "bytes_out": 0}
        # layer span of a program compile: the owning ingress pipeline
        # binds its own (``Observability.span`` under its shard)
        self.span = no_span
        self._process = jax.jit(self._process_impl,
                                static_argnames=("use_mlp", "use_forest"))
        self._serve = jax.jit(self._serve_impl,
                              static_argnames=("use_mlp", "use_forest"))
        # compiled feature-path programs, one per (rows, use_mlp,
        # use_forest): compiled ahead of their first dispatch, so a
        # lowering error surfaces as a CompileError before the batch runs
        self._programs: dict = {}

    # -- the data plane ----------------------------------------------------

    def _serve_impl(self, x0: jax.Array, model_id: jax.Array,
                    tables: ModelTables, ftables: "ForestTables | None",
                    rtables, use_mlp: bool, use_forest: bool) -> jax.Array:
        """The feature-path program: lane dispatch only (one device
        dispatch per staged batch; the byte codec runs once per chunk on
        the host — ``parse_packets_np``/``emit_results_np``)."""
        self.trace_count += 1  # python side effect: fires once per trace
        return serve_lanes(x0, model_id, tables, ftables, rtables,
                           self.lane_cfg, use_mlp=use_mlp,
                           use_forest=use_forest)

    def _process_impl(self, pkts: jax.Array, tables: ModelTables,
                      ftables: "ForestTables | None", rtables,
                      use_mlp: bool, use_forest: bool) -> jax.Array:
        self.trace_count += 1  # python side effect: fires once per trace
        parsed = parse_packets(pkts, self.max_features)
        outputs = serve_lanes(parsed.features_q, parsed.model_id, tables,
                              ftables, rtables, self.lane_cfg,
                              use_mlp=use_mlp, use_forest=use_forest)
        return emit_results(parsed, outputs, self.frac)

    def _lane_flags(self, lanes: str):
        """Resolve the lane hint against the monotone forest switch.  One
        ``forest_active`` read: deriving both flags from two reads could
        interleave with the first-ever install_forest and disable both
        lanes."""
        forest_active = self.cp.forest_active
        use_forest = lanes != "mlp" and forest_active
        use_mlp = lanes != "forest" or not forest_active
        return use_mlp, use_forest

    def _forest_snapshots(self, use_forest: bool):
        """Consistent (ftables, rtables) pair for the forest lane — one
        control-plane lock acquisition, so a racing ``install_forest`` can
        never hand the range variant liveness from one generation and range
        rows from another (stale-but-consistent is safe; torn is not)."""
        if not use_forest:
            return None, None
        return self.cp.forest_snapshots(self.forest_variant == "range",
                                        device=self.device)

    def _place(self, arr, dtype) -> jax.Array:
        """One batch operand as a ``dtype`` device array, committed to this
        engine's device; a host array goes straight there, not by way of
        the default device.  Unplaced, it follows the uncommitted
        default."""
        if self.device is None:
            return jnp.asarray(arr, dtype)
        if isinstance(arr, jax.Array):
            return jax.device_put(arr.astype(dtype), self.device)
        return jax.device_put(np.asarray(arr, dtype), self.device)

    # -- host API -----------------------------------------------------------

    def process(self, pkts, *, lanes: str = "both") -> jax.Array:
        """Run one mixed-model batch of ingress packets → egress packets
        (the wire path: byte parse/deparse inside the program), blocking
        until the egress is ready.

        ``lanes`` is the lane-pure dispatch hint: ``"both"`` (default —
        correct for any batch), ``"mlp"`` or ``"forest"`` skip the other
        family's compute for batches the caller *knows* are single-family.
        Each lane combination is one more static jit variant — bounded at
        three, warmed once each.
        """
        if lanes not in ("both", "mlp", "forest"):
            raise ValueError(f"unknown lanes hint: {lanes!r}")
        pkts = self._place(pkts, jnp.uint8)
        tables = self.cp.tables(device=self.device)  # current generation
        use_mlp, use_forest = self._lane_flags(lanes)
        ftables, rtables = self._forest_snapshots(use_forest)
        out = self._process(pkts, tables, ftables, rtables, use_mlp=use_mlp,
                            use_forest=use_forest)
        self.stats["packets"] += int(pkts.shape[0])
        self.stats["bytes_in"] += int(pkts.size)
        self.stats["bytes_out"] += int(out.size)
        out.block_until_ready()
        return out

    def run_features(self, feats_q, model_id, *, block: bool = True,
                     lanes: str = "both") -> jax.Array:
        """Run one mixed-model batch of **already-parsed** feature codes —
        the feature path: one pure-compute device dispatch, no byte codec
        in the program (the cold-path tentpole; the ingress pipeline's
        serving entry).

        feats_q (B, W) int32 codes at the engine's ``frac`` · model_id (B,)
        int32 → device future of (B, out_features) int32 output codes.
        Byte counters credit the equivalent wire row sizes, so they stay
        comparable across the two surfaces.

        With ``block=False`` the future's device-to-host copy is started
        before return: every caller reads the result on the host, and the
        copy then runs behind the caller's next host work.
        """
        if lanes not in ("both", "mlp", "forest"):
            raise ValueError(f"unknown lanes hint: {lanes!r}")
        feats_q = self._place(feats_q, jnp.int32)
        model_id = self._place(model_id, jnp.int32)
        tables = self.cp.tables(device=self.device)
        use_mlp, use_forest = self._lane_flags(lanes)
        args = (feats_q, model_id, tables,
                *self._forest_snapshots(use_forest))
        program = self._program(args, use_mlp, use_forest)
        out = program(*args)
        n = int(feats_q.shape[0])
        self.stats["packets"] += n
        self.stats["bytes_in"] += n * (HEADER_BYTES
                                       + FEATURE_BYTES * self.max_features)
        self.stats["bytes_out"] += n * (HEADER_BYTES
                                        + FEATURE_BYTES * self.out_features)
        if block:
            out.block_until_ready()
        else:
            start_copy = getattr(out, "copy_to_host_async", None)
            if start_copy is not None:  # a test double may have none
                try:
                    start_copy()
                except Exception:  # noqa: BLE001 — the batch is credited
                    # already: whatever broke the copy raises again at the
                    # caller's read, where the retire salvages it once
                    pass
        return out

    def _program(self, args, use_mlp: bool, use_forest: bool):
        key = (int(args[0].shape[0]), use_mlp, use_forest)
        program = self._programs.get(key)
        if program is None:
            with self.span("engine.compile"):
                try:
                    program = self._serve.lower(
                        *args, use_mlp=use_mlp,
                        use_forest=use_forest).compile()
                except Exception as e:  # noqa: BLE001 — any lowering failure
                    raise CompileError(
                        f"serving program ({key[0]} rows, lanes="
                        f"{_LANE_NAMES[key[1:]]}) failed to compile on "
                        f"{jax.default_backend()}: {e}") from e
            self._programs[key] = program
        return program

    def compile(self, n_rows: int, lanes: str = "both"):
        """Compile the feature-path program for ``n_rows``-row batches on
        the ``lanes`` hint (as the control plane resolves it now), unless
        it already exists; returns it.  Raises :class:`CompileError`."""
        use_mlp, use_forest = self._lane_flags(lanes)
        program = self._programs.get((n_rows, use_mlp, use_forest))
        if program is not None:  # the per-dispatch check stays cheap
            return program
        feats_q = self._place(np.zeros((n_rows, self.max_features)),
                              jnp.int32)
        model_id = self._place(np.zeros(n_rows), jnp.int32)
        return self._program(
            (feats_q, model_id, self.cp.tables(device=self.device),
             *self._forest_snapshots(use_forest)), use_mlp, use_forest)

    def compiled_programs(self) -> dict:
        """Every compiled feature-path program, keyed ``(rows, lanes)``
        with lanes ``"mlp"``, ``"forest"`` or ``"both"``."""
        return {(k[0], _LANE_NAMES[k[1:]]): v
                for k, v in self._programs.items()}

    def warm(self, batch_size: int, wire_len: int, *,
             lanes: Sequence[str] = ("both",),
             feature_batches: "Sequence[int] | None" = None) -> None:
        """Pre-trace the jit variants a serving loop will hit (one per
        ``(shape, lanes)`` combination) on a dead batch, outside any timed
        window — both surfaces: the wire program at ``batch_size`` rows and
        the feature program (``run_features``, what the ingress pipeline
        dispatches) at every size in ``feature_batches`` (default: just
        ``batch_size``; pass the pipeline's ``batch_sizes`` ladder when
        adaptive sizing is on, or ``()`` to skip).  Stats are rolled back:
        warming is not traffic.  Benchmarks and latency-sensitive
        deployments call this so the first real batch never pays the
        compile."""
        if feature_batches is None:
            feature_batches = (batch_size,)
        pkts = jnp.zeros((batch_size, wire_len), jnp.uint8)
        before = dict(self.stats)
        for lane in lanes:
            self.process(pkts, lanes=lane)
            for fb in feature_batches:
                x0 = jnp.zeros((fb, self.max_features), jnp.int32)
                mid = jnp.zeros((fb,), jnp.int32)
                self.run_features(x0, mid, block=True, lanes=lane)
        self.stats = before

    def credit_packets(self, n: int) -> None:
        """Adjust the served-packet counter on behalf of the ingress
        pipeline: positive for packets it served without a device dispatch
        (cache hits, coalesced duplicates), negative for dead padding rows
        inside a dispatched batch — so the counter reflects packets
        actually served, not device rows."""
        self.stats["packets"] += int(n)

    def credit_bytes(self, n_in: int, n_out: int) -> None:
        """Byte-counter analogue of :meth:`credit_packets` — the pipeline
        uses a negative credit to cancel a dispatch it discarded (the
        lane-race redispatch), so the byte counters never double-count the
        dropped batch's wire bytes."""
        self.stats["bytes_in"] += int(n_in)
        self.stats["bytes_out"] += int(n_out)

