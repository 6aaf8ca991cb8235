"""Control-plane weight tables (paper §2, §3 item 3, Fig 2).

The paper's defining systems property: model parameters (weights, biases,
Taylor constants) live in *control-plane table lookups*, so a model can be
retrained and re-installed at runtime **without re-synthesizing the data
plane**.  The TPU translation (DESIGN.md §2):

  * the compiled XLA program is the data plane — compiling it is the analogue
    of FPGA synthesis;
  * every parameter is a **traced argument** of that program (never a
    closed-over constant), padded to static table shapes;
  * ``ControlPlane.install()`` writes new quantized tables; the next batch
    simply receives different buffers — the jit cache never misses.

Tests assert the "no re-synthesis" property by counting traces.

Three table families:

  * :class:`ModelTables` (owned by :class:`ControlPlane`) — the paper-scale
    family: up to ``max_models`` MLP/regression models (Model ID-addressed),
    stacked into dense padded tables so one compiled program serves every
    installed model.
  * :class:`ForestTables` (also owned by :class:`ControlPlane`) — the
    tree-ensemble family (pForest/Planter analogue): up to ``max_forests``
    random forests packed into dense padded node tables
    (feature | threshold | left | right | leaf per node), installed with
    the **same** generation-swap protocol and sharing the same generation
    counter, so ingress caches keyed on ``version`` cover both families.
  * :class:`WeightRegistry` — the LM-scale generalization used by
    ``launch/serve.py``: named parameter pytrees with hot-swap semantics.

Model IDs form one namespace across the MLP and forest families: a given ID
resolves to exactly one of the two ``id_map`` tables (installing it in the
other family first requires ``remove()``), which is what lets the data plane
route a mixed batch per packet.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.ref import N_FLOW_FEATURES
from .fixedpoint import FixedPointFormat, encode

__all__ = [
    "ACT_NONE",
    "ACT_RELU",
    "ACT_SIGMOID",
    "ACT_LEAKY_RELU",
    "ACT_HARD_SIGMOID",
    "ACTIVATIONS",
    "ModelTables",
    "ForestTables",
    "RangeTables",
    "FeatureSpec",
    "ControlPlane",
    "WeightRegistry",
]

# Activation opcodes stored per (model, layer) in the action table.
ACT_NONE = 0
ACT_RELU = 1
ACT_SIGMOID = 2  # Taylor-approximated (order is a data-plane config)
ACT_LEAKY_RELU = 3
ACT_HARD_SIGMOID = 4

ACTIVATIONS = {
    "none": ACT_NONE,
    "relu": ACT_RELU,
    "sigmoid": ACT_SIGMOID,
    "leaky_relu": ACT_LEAKY_RELU,
    "hard_sigmoid": ACT_HARD_SIGMOID,
}


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ModelTables:
    """Dense, padded, device-resident parameter tables (the match-action RAM).

    Shapes (``M`` models, ``L`` layers, ``W`` width):
      * ``w``        (M, L, W, W)  weight codes (symmetric fixed-point)
      * ``b``        (M, L, W)     bias codes at ``2*frac`` fractional bits
                                   (pre-shifted so they add directly onto the
                                   int32 accumulator of a W×W product)
      * ``act``      (M, L)        activation opcodes
      * ``layer_on`` (M, L)        1 if the layer exists for this model
      * ``out_dim``  (M,)          number of output features
      * ``id_map``   (65536,)      Model-ID → table slot (-1 = not installed)
    """

    w: jax.Array
    b: jax.Array
    act: jax.Array
    layer_on: jax.Array
    out_dim: jax.Array
    id_map: jax.Array

    def tree_flatten(self):
        return ((self.w, self.b, self.act, self.layer_on, self.out_dim, self.id_map), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ForestTables:
    """Dense, padded, device-resident tree-ensemble tables (the
    pForest/Planter match-action RAM).

    Shapes (``F`` forests, ``T`` trees, ``N`` nodes):
      * ``nodes``    (F, T, N, 5)  int32 node records — field order
                                   feature | quantized threshold | left |
                                   right | leaf payload; leaves self-loop
                                   (left == right == self)
      * ``tree_on``  (F, T)        1 if the tree exists for this forest
      * ``mode``     (F,)          vote mode (kernels.ref.FOREST_REGRESS /
                                   FOREST_CLASSIFY)
      * ``out_dim``  (F,)          output lanes (1 or n_classes)
      * ``id_map``   (65536,)      Model-ID → forest slot (-1 = not a forest)
    """

    nodes: jax.Array
    tree_on: jax.Array
    mode: jax.Array
    out_dim: jax.Array
    id_map: jax.Array

    def tree_flatten(self):
        return ((self.nodes, self.tree_on, self.mode, self.out_dim,
                 self.id_map), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class RangeTables:
    """Device-resident range-table compilation of the forest family (the
    pForest ternary-match lowering — see ``repro.forest.ranges``).

    Compiled alongside :class:`ForestTables` on every ``install_forest`` and
    published by the **same** generation swap, so the two lowerings of one
    ensemble can never be out of sync.  Shapes (``F`` forests, ``T`` trees,
    ``NI = (max_nodes-1)//2`` range entries, ``L = NI+1`` leaves):

      * ``feat``     (F, T, NI)  int32 feature index per range entry
      * ``thresh``   (F, T, NI)  int32 threshold code (padding: INT32_MAX —
                                 the comparison always holds, mask unused)
      * ``lmask``    (F, T, NI)  uint32 surviving-leaf mask when the entry's
                                 ``x <= thresh`` comparison fails
      * ``payload``  (F, T, L)   int32 per-leaf output codes (in-order
                                 leaf numbering — exit leaf = lowest set bit)

    Tree liveness, vote mode, output dims and the Model-ID map are shared
    with :class:`ForestTables` (one forest family, two lowerings).
    """

    feat: jax.Array
    thresh: jax.Array
    lmask: jax.Array
    payload: jax.Array

    def tree_flatten(self):
        return ((self.feat, self.thresh, self.lmask, self.payload), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@dataclasses.dataclass(frozen=True)
class FeatureSpec:
    """Flow-feature → model-input column mapping (the Planter "feature
    mapping stage" as its own control-plane object).

    ``columns[j]`` names the flow-engine feature lane
    (``kernels.ref.FLOW_FEATURE_NAMES`` order) that feeds the model's input
    column ``j``.  Installed per Model ID with the same generation-swap
    discipline as the weight tables, so an MLP and a forest can consume
    *different* register subsets from one shared flow table, and
    re-mapping a live model is one host-side swap — no data-plane retrace
    (the wire shape never changes; the parser masks unused columns).
    """

    columns: Tuple[int, ...]

    def __post_init__(self):
        if not self.columns:
            raise ValueError("FeatureSpec needs at least one column")
        for c in self.columns:
            if not 0 <= int(c) < N_FLOW_FEATURES:
                raise ValueError(
                    f"FeatureSpec column {c} outside the flow engine's "
                    f"[0, {N_FLOW_FEATURES}) feature lanes")


class ControlPlane:
    """Host-side registry that owns and mutates the model tables.

    ``frac_bits`` is shared by features and weights — the paper: "To reduce
    arbitration, we assume input features and weights follow the same
    fractional and integer bits."

    Installs are **double-buffered**: a writer mutates a *copy* of the live
    host tables and atomically swaps the front pointer (bumping the
    generation counter).  ``tables()`` returns a device snapshot cached per
    generation, so (a) a batch in flight keeps the old device buffers — an
    ``install()`` racing it can never tear a table mid-inference — and (b)
    steady-state serving re-uploads nothing: the same device buffers are
    re-fed to the jit'd data plane until a writer publishes a new
    generation.  Shapes never change, so swaps cause zero retraces.
    """

    def __init__(self, *, max_models: int = 16, max_layers: int = 4,
                 max_width: int = 32, weight_bits: int = 16, frac_bits: int = 8,
                 max_forests: int = 8, max_trees: int = 16,
                 max_nodes: int = 64, max_tree_depth: int = 6):
        self.max_models = max_models
        self.max_layers = max_layers
        self.max_width = max_width
        self.fmt = FixedPointFormat(total_bits=weight_bits, frac_bits=frac_bits)
        self.frac_bits = frac_bits
        self._lock = threading.Lock()
        # fault-injection hook (serve.faults.FaultPlan.install attaches it);
        # fired between table preparation and the commit point of every
        # install so the all-or-nothing swap property is testable
        self.fault_plan = None
        # obs EventLog hook (a serving wrapper attaches its shared log):
        # every committed table swap — the generation bumps — is recorded
        # so a failover/install history reconstructs from the log alone
        self.events = None
        # install listeners (PR 9): ``fn(kind, model_id)`` callbacks run
        # after every committed swap — the drift monitor hooks here to
        # freeze its reference window at install time
        self.install_listeners: List = []
        w_dtype = np.dtype(self.fmt.dtype)
        self._w = np.zeros((max_models, max_layers, max_width, max_width), w_dtype)
        self._b = np.zeros((max_models, max_layers, max_width), np.int32)
        self._act = np.zeros((max_models, max_layers), np.int32)
        self._layer_on = np.zeros((max_models, max_layers), np.int32)
        self._out_dim = np.zeros((max_models,), np.int32)
        self._id_map = np.full((65536,), -1, np.int32)
        self._slots: Dict[int, int] = {}
        self._free_slots: List[int] = []  # recycled by remove()
        self._next_slot = 0
        # -- tree-ensemble family (same swap discipline, shared generation) --
        self.max_forests = max_forests
        self.max_trees = max_trees
        self.max_nodes = max_nodes
        self.max_tree_depth = max_tree_depth
        self._f_nodes = np.zeros((max_forests, max_trees, max_nodes, 5),
                                 np.int32)
        self._f_tree_on = np.zeros((max_forests, max_trees), np.int32)
        self._f_mode = np.zeros((max_forests,), np.int32)
        self._f_out_dim = np.zeros((max_forests,), np.int32)
        self._f_id_map = np.full((65536,), -1, np.int32)
        # range-table lowering of the same family (pForest ternary-match —
        # repro.forest.ranges).  Static extents derive from max_nodes; the
        # 32-bit leaf mask caps the lane at 32 leaves per tree, so planes
        # with a larger node budget simply don't compile the range family
        # (the pointer-chase lane has no such bound).
        from ..forest.ranges import range_bounds
        ni, nl = range_bounds(max_nodes)
        self._r_ni, self._r_nl = max(1, ni), max(1, nl)
        self.range_available = nl <= 32
        if self.range_available:
            self._r_feat = np.zeros((max_forests, max_trees, self._r_ni),
                                    np.int32)
            self._r_th = np.full((max_forests, max_trees, self._r_ni),
                                 np.iinfo(np.int32).max, np.int32)
            self._r_mask = np.zeros((max_forests, max_trees, self._r_ni),
                                    np.uint32)
            self._r_payload = np.zeros((max_forests, max_trees, self._r_nl),
                                       np.int32)
        self._f_slots: Dict[int, int] = {}
        self._f_free_slots: List[int] = []
        self._f_next_slot = 0
        # latched on the first forest install; the engine keys its static
        # "compile the forest lane" decision off this, so it is monotone —
        # at most one extra trace over the process lifetime, never a flap
        self._forest_ever = False
        # -- flow feature-spec family (host-only: consumed by the flow
        #    frontend, never uploaded to the device — an install is still a
        #    generation swap so readers see one coherent mapping) --
        self._spec_map = np.full((65536,), -1, np.int32)
        self._spec_rows = np.full((0, max_width), -1, np.int32)
        self._spec_lens = np.zeros((0,), np.int32)
        self._specs: Dict[int, "FeatureSpec"] = {}
        # per-generation read LUT (identity row prepended so slot -1 maps
        # to it via +1): the frontend's hot path is one gather, no masks
        self._spec_read_cache: Optional[Tuple] = None
        # -- latency-SLO family (host-only: per-model deadline budgets in
        #    microseconds consumed by the ingress deadline scheduler; inf =
        #    no budget installed, so unbudgeted traffic reads as "never
        #    closes a batch early" with zero branches) --
        self._slo_us = np.full((65536,), np.inf, np.float64)
        self._slo_models: Dict[int, float] = {}
        self._slo_any = False  # monotone: ingress gates its deadline math
        # -- reflex family (host-only: per-model threshold/rule programs
        #    answering in host microseconds when the model lane would blow
        #    the budget — serve.reflex.ReflexProgram packed into dense
        #    padded arrays, same prepare-then-commit swap discipline) --
        self._rx_map = np.full((65536,), -1, np.int32)
        self._rx_lane = np.zeros((0, max_width), np.int32)
        self._rx_thr = np.zeros((0, max_width), np.int32)
        self._rx_w = np.zeros((0, max_width), np.int32)
        self._rx_bias = np.zeros((0,), np.int64)
        self._rx_true = np.zeros((0, max_width), np.int32)
        self._rx_false = np.zeros((0, max_width), np.int32)
        self._rx_out_dim = np.zeros((0,), np.int32)
        self._rx_programs: Dict[int, object] = {}
        self._rx_any = False   # monotone: ingress gates its reflex lane
        self._rx_read_cache: Optional[Tuple] = None
        self._version = 0
        # per-family write counters: the shared `_version` is the cache/
        # staleness key (one counter must cover both families), but device
        # snapshots re-upload per *family* generation, so hot-swapping one
        # family never re-uploads the other's unchanged tables
        self._mlp_gen = 0
        self._forest_gen = 0
        # per-device snapshot caches (key None = the default device).  One
        # control plane can feed N engine shards on N devices: each device
        # gets its own cached upload of the SAME host generation, so a
        # broadcast install is one host write + one lazy upload per shard —
        # and the shared ``_version`` counter is the cross-shard generation
        # fence (no per-shard version can ever diverge, because there is
        # only one).
        self._snapshot: Dict[Optional[object],
                             Tuple[int, "ModelTables"]] = {}
        self._forest_snapshot: Dict[Optional[object],
                                    Tuple[int, "ForestTables"]] = {}
        self._range_snapshot: Dict[Optional[object],
                                   Tuple[int, "RangeTables"]] = {}

    def _fire_fault(self, site: str) -> None:
        """Fault-injection hook (no-op without an installed plan).  Sits at
        the last point before an install's commit block: anything it raises
        must leave the live tables bit-identical and the version counter
        unchanged — the crash-safety property the chaos tests assert."""
        plan = self.fault_plan
        if plan is not None:
            plan.fire(site, shard=-1)

    def _emit(self, kind: str, model_id: int, **detail) -> None:
        """Record a committed table swap in the attached event log (no-op
        without one) and notify install listeners.  Called *after* the
        version bump, so the event's generation is the one the swap
        published."""
        events = self.events
        if events is not None:
            events.emit(kind, shard=-1, generation=self._version,
                        model_id=int(model_id), **detail)
        for fn in list(self.install_listeners):
            fn(kind, int(model_id))

    def _begin_write(self) -> None:
        """Copy-on-write: detach the MLP-family back buffers from any
        published snapshot before mutating (caller holds the lock)."""
        self._w = self._w.copy()
        self._b = self._b.copy()
        self._act = self._act.copy()
        self._layer_on = self._layer_on.copy()
        self._out_dim = self._out_dim.copy()
        self._id_map = self._id_map.copy()

    def _begin_write_forest(self) -> None:
        """Copy-on-write for the forest-family back buffers (both
        lowerings: dense node tables and range tables swap together)."""
        self._f_nodes = self._f_nodes.copy()
        self._f_tree_on = self._f_tree_on.copy()
        self._f_mode = self._f_mode.copy()
        self._f_out_dim = self._f_out_dim.copy()
        self._f_id_map = self._f_id_map.copy()
        if self.range_available:
            self._r_feat = self._r_feat.copy()
            self._r_th = self._r_th.copy()
            self._r_mask = self._r_mask.copy()
            self._r_payload = self._r_payload.copy()

    # -- control-plane writes -------------------------------------------

    def install(self, model_id: int,
                layers: Sequence[Tuple[np.ndarray, np.ndarray]],
                activations: Sequence[str],
                final_activation: str = "none",
                slo_budget_us: Optional[float] = None) -> int:
        """Quantize and install (or hot-swap) a model. Returns its slot.

        ``layers``: [(W0, b0), …] with ``W_l`` of shape (in, out) floats.
        ``activations``: one name per hidden layer; the last layer uses
        ``final_activation``.  ``slo_budget_us`` optionally installs the
        model's latency budget in the same generation swap (see
        :meth:`install_slo_budget`).
        """
        slo = self._check_slo(slo_budget_us)
        if len(layers) > self.max_layers:
            raise ValueError(f"model has {len(layers)} layers > max {self.max_layers}")
        acts = list(activations) + [final_activation]
        acts = acts[: len(layers)]
        # Validate + quantize everything BEFORE touching any table state, so
        # a bad model can never leave a half-installed network behind (the
        # generation swap must be all-or-nothing).
        quantized = []
        for l, (w, bias) in enumerate(layers):
            w = np.asarray(w, np.float32)
            bias = np.asarray(bias, np.float32)
            din, dout = w.shape
            if din > self.max_width or dout > self.max_width:
                raise ValueError(f"layer {l} ({din}x{dout}) exceeds max width")
            opcode = ACTIVATIONS[acts[l]]  # KeyError before any mutation
            wq = np.asarray(encode(w, self.frac_bits, total_bits=self.fmt.total_bits))
            # bias pre-shifted onto the accumulator grid (2*frac bits)
            bq = np.asarray(encode(bias, 2 * self.frac_bits, total_bits=32))
            quantized.append((din, dout, wq, bq, opcode))
        with self._lock:
            if model_id in self._f_slots:
                raise ValueError(
                    f"model id {model_id} is installed as a forest — "
                    "remove() it before installing an MLP under the same id")
            slot = self._slots.get(model_id)
            if slot is None and not self._free_slots \
                    and self._next_slot >= self.max_models:
                raise ValueError("control plane table full")
            # Prepare on private copies; the commit block below is plain
            # exception-free assignments, so an exception anywhere up to
            # (and including) the fault hook rolls back for free: live
            # tables bit-identical, version unchanged, zero retraces.
            w, b, act = self._w.copy(), self._b.copy(), self._act.copy()
            layer_on = self._layer_on.copy()
            out_dim, id_map = self._out_dim.copy(), self._id_map.copy()
            slots, free = dict(self._slots), list(self._free_slots)
            next_slot = self._next_slot
            if slot is None:
                # prefer recycled slots: a fresh index for every install
                # would collide live models once remove() had been used
                slot = free.pop() if free else next_slot
                if slot == next_slot:
                    next_slot += 1
                slots[model_id] = slot
                id_map[model_id] = slot
            w[slot] = 0
            b[slot] = 0
            layer_on[slot] = 0
            for l, (din, dout, wq, bq, opcode) in enumerate(quantized):
                w[slot, l, :din, :dout] = wq
                b[slot, l, :dout] = bq
                act[slot, l] = opcode
                layer_on[slot, l] = 1
            out_dim[slot] = layers[-1][0].shape[1]
            slo_us = self._prep_slo(model_id, slo)
            self._fire_fault("install")
            # -- commit (atomic under the lock) --
            self._w, self._b, self._act = w, b, act
            self._layer_on, self._out_dim = layer_on, out_dim
            self._id_map = id_map
            self._slots, self._free_slots = slots, free
            self._next_slot = next_slot
            self._commit_slo(model_id, slo, slo_us)
            self._mlp_gen += 1
            self._version += 1
            self._emit("install", model_id, family="mlp", slot=slot)
            return slot

    def installed_ids(self) -> frozenset:
        """Model ids currently installed in either family — the admission
        whitelist for strict serving surfaces (a raw row naming any other
        id would ride an uninstalled slot to all-zero egress)."""
        with self._lock:
            return frozenset(self._slots) | frozenset(self._f_slots)

    def remove(self, model_id: int) -> None:
        """Uninstall a model from whichever family holds it (no-op if
        neither does)."""
        with self._lock:
            slot = self._slots.pop(model_id, None)
            if slot is not None:
                self._begin_write()
                self._id_map[model_id] = -1
                self._layer_on[slot] = 0
                self._free_slots.append(slot)
                self._mlp_gen += 1
                self._version += 1
                self._emit("remove", model_id, family="mlp")
                return
            fslot = self._f_slots.pop(model_id, None)
            if fslot is None:
                return
            self._begin_write_forest()
            self._f_id_map[model_id] = -1
            self._f_tree_on[fslot] = 0
            self._f_free_slots.append(fslot)
            self._forest_gen += 1
            self._version += 1
            self._emit("remove", model_id, family="forest")

    # -- tree-ensemble family -------------------------------------------

    def install_forest(self, model_id: int, forest,
                       slo_budget_us: Optional[float] = None) -> int:
        """Quantize, pack and install (or hot-swap) a tree ensemble.
        Returns its forest slot.

        ``forest`` is a :class:`repro.forest.Forest` (packed here against
        this plane's ``frac_bits``) or a pre-built
        :class:`repro.forest.PackedForest`.  Same all-or-nothing
        generation-swap discipline as :meth:`install`: everything is
        validated and quantized before any table state is touched, and the
        swap is one version bump — an in-flight batch keeps the old device
        buffers, the next batch sees the new forest, zero retraces.
        """
        from ..forest.compile import Forest, PackedForest, pack_forest
        if isinstance(forest, Forest):
            packed = pack_forest(forest, frac_bits=self.frac_bits)
        elif isinstance(forest, PackedForest):
            packed = forest
        else:
            raise TypeError(
                f"install_forest wants a Forest or PackedForest, "
                f"got {type(forest).__name__}")
        n_trees, n_nodes, _ = packed.nodes.shape
        if n_trees > self.max_trees:
            raise ValueError(
                f"forest has {n_trees} trees > max {self.max_trees}")
        if n_nodes > self.max_nodes:
            raise ValueError(
                f"forest has {n_nodes}-node trees > max {self.max_nodes}")
        if packed.depth > self.max_tree_depth:
            raise ValueError(
                f"forest depth {packed.depth} exceeds the data plane's "
                f"unroll bound max_tree_depth={self.max_tree_depth}")
        if packed.frac_bits != self.frac_bits:
            raise ValueError(
                f"forest packed at {packed.frac_bits} fractional bits; "
                f"this control plane's wire grid is {self.frac_bits}")
        feats = packed.nodes[:, :, 0]
        if feats.size and (int(feats.max()) >= self.max_width
                           or int(feats.min()) < 0):
            raise ValueError(
                f"forest splits on feature {int(feats.max())} >= "
                f"max_width={self.max_width}")
        kids = packed.nodes[:, :, 2:4]
        if kids.size and (int(kids.min()) < 0
                          or int(kids.max()) >= n_nodes):
            raise ValueError(
                "forest child pointers outside [0, n_nodes) — leaves must "
                "self-loop (pack_forest does this); dangling pointers would "
                "break the level-bounded traversal")
        if packed.mode == 1:  # FOREST_CLASSIFY: leaves are vote-lane indices
            leaves = packed.nodes[:, :, 4]
            if leaves.size and (int(leaves.min()) < 0
                                or int(leaves.max()) >= packed.out_dim):
                raise ValueError(
                    f"classification leaf label outside [0, "
                    f"{packed.out_dim}) — an out-of-range label would vote "
                    "into a masked-off (or nonexistent) lane and silently "
                    "vanish at egress")
        if packed.out_dim > self.max_width:
            raise ValueError(
                f"forest out_dim {packed.out_dim} exceeds "
                f"max_width={self.max_width} vote lanes")
        # Range-table compilation (pForest lowering) happens here, BEFORE any
        # table state is touched: it also walk-validates tree structure
        # (acyclicity, per-node depth, leaf budget) that the dense-table
        # bounds checks above cannot see, so a malformed PackedForest fails
        # the install instead of serving garbage through either lane.
        slo = self._check_slo(slo_budget_us)
        ranges = None
        if self.range_available:
            from ..forest.ranges import pack_forest_ranges
            ranges = pack_forest_ranges(packed.nodes, packed.tree_on,
                                        max_depth=self.max_tree_depth)
        with self._lock:
            if model_id in self._slots:
                raise ValueError(
                    f"model id {model_id} is installed as an MLP — "
                    "remove() it before installing a forest under the "
                    "same id")
            slot = self._f_slots.get(model_id)
            if slot is None and not self._f_free_slots \
                    and self._f_next_slot >= self.max_forests:
                raise ValueError("forest table full")
            # prepare-then-commit, same crash-safety contract as install():
            # BOTH lowerings stage on private copies and publish together
            f_nodes = self._f_nodes.copy()
            f_tree_on = self._f_tree_on.copy()
            f_mode, f_out_dim = self._f_mode.copy(), self._f_out_dim.copy()
            f_id_map = self._f_id_map.copy()
            f_slots, f_free = dict(self._f_slots), list(self._f_free_slots)
            f_next = self._f_next_slot
            if slot is None:
                slot = f_free.pop() if f_free else f_next
                if slot == f_next:
                    f_next += 1
                f_slots[model_id] = slot
                f_id_map[model_id] = slot
            f_nodes[slot] = 0
            f_tree_on[slot] = 0
            f_nodes[slot, :n_trees, :n_nodes] = packed.nodes
            f_tree_on[slot, :n_trees] = packed.tree_on
            f_mode[slot] = packed.mode
            f_out_dim[slot] = packed.out_dim
            if ranges is not None:
                r_feat, r_th = self._r_feat.copy(), self._r_th.copy()
                r_mask = self._r_mask.copy()
                r_payload = self._r_payload.copy()
                r_feat[slot] = 0
                r_th[slot] = np.iinfo(np.int32).max
                r_mask[slot] = 0
                r_payload[slot] = 0
                ni = ranges.feat.shape[1]
                nl = ranges.payload.shape[1]
                r_feat[slot, :n_trees, :ni] = ranges.feat
                r_th[slot, :n_trees, :ni] = ranges.thresh
                r_mask[slot, :n_trees, :ni] = ranges.lmask
                r_payload[slot, :n_trees, :nl] = ranges.payload
            slo_us = self._prep_slo(model_id, slo)
            self._fire_fault("install")
            # -- commit (atomic under the lock) --
            self._f_nodes, self._f_tree_on = f_nodes, f_tree_on
            self._f_mode, self._f_out_dim = f_mode, f_out_dim
            self._f_id_map = f_id_map
            self._f_slots, self._f_free_slots = f_slots, f_free
            self._f_next_slot = f_next
            if ranges is not None:
                self._r_feat, self._r_th = r_feat, r_th
                self._r_mask, self._r_payload = r_mask, r_payload
            self._commit_slo(model_id, slo, slo_us)
            self._forest_ever = True
            self._forest_gen += 1
            self._version += 1
            self._emit("install_forest", model_id, family="forest",
                       slot=slot)
            return slot

    def is_forest_id(self, model_ids: np.ndarray) -> np.ndarray:
        """Vectorized host-side family lookup (current generation): True
        where a Model ID resolves to a forest slot.  The ingress pipeline
        uses this to stage lane-pure device batches; staleness is handled
        there (a batch whose staging generation is not the dispatch
        generation falls back to a both-lane dispatch)."""
        with self._lock:
            return self._f_id_map[np.asarray(model_ids, np.int64)] >= 0

    # -- flow feature-spec family ---------------------------------------

    def install_feature_spec(self, model_id: int, spec) -> int:
        """Install (or hot-swap) the :class:`FeatureSpec` mapping flow-engine
        feature lanes onto ``model_id``'s input columns.  Returns the spec
        slot.

        Same write discipline as the table families — validate everything,
        copy-on-write, one version bump — but the spec family is host-only
        state read by the flow frontend: a reinstall publishes a new mapping
        for the *next* submitted raw batch and can never retrace the data
        plane (the wire shape is fixed; only the bytes inside it change).
        The version bump conservatively orphans cached egress rows built
        under the old mapping's wire rows.

        A spec outlives ``remove()`` of its model: the mapping belongs to
        the Model ID (a retrained model reinstalled under the same id keeps
        consuming the same registers) — drop it explicitly with
        :meth:`remove_feature_spec`.
        """
        if not isinstance(spec, FeatureSpec):
            spec = FeatureSpec(columns=tuple(int(c) for c in spec))
        if not 0 <= int(model_id) < 65536:
            raise ValueError(f"model id {model_id} outside the 16-bit "
                             "Model ID field")
        if len(spec.columns) > self.max_width:
            raise ValueError(
                f"FeatureSpec has {len(spec.columns)} columns > "
                f"max_width={self.max_width} input lanes")
        with self._lock:
            # prepare-then-commit (same crash-safety contract as install())
            smap = self._spec_map
            rows, lens = self._spec_rows.copy(), self._spec_lens.copy()
            slot = int(smap[model_id])
            if slot < 0:  # the map only changes when a new slot is minted
                smap = smap.copy()
                slot = rows.shape[0]
                rows = np.concatenate(
                    [rows, np.full((1, self.max_width), -1, np.int32)])
                lens = np.concatenate([lens, np.zeros(1, np.int32)])
                smap[model_id] = slot
            rows[slot] = -1
            rows[slot, : len(spec.columns)] = spec.columns
            lens[slot] = len(spec.columns)
            self._fire_fault("install")
            # -- commit (atomic under the lock) --
            self._spec_map, self._spec_rows, self._spec_lens = \
                smap, rows, lens
            self._specs[model_id] = spec
            self._version += 1
            self._emit("install_feature_spec", model_id, slot=slot)
            return slot

    def remove_feature_spec(self, model_id: int) -> None:
        """Uninstall a feature spec; the model id falls back to the identity
        mapping (no-op if none installed)."""
        with self._lock:
            if self._specs.pop(model_id, None) is None:
                return
            self._spec_map = self._spec_map.copy()
            self._spec_map[model_id] = -1  # row slot retired (specs are tiny)
            self._version += 1
            self._emit("remove", model_id, family="spec")

    def feature_spec(self, model_id: int) -> Optional[FeatureSpec]:
        with self._lock:
            return self._specs.get(model_id)

    def feature_spec_rows(self, model_ids: np.ndarray, width: int
                          ) -> Tuple[np.ndarray, np.ndarray]:
        """Vectorized per-packet spec gather for the flow frontend: returns
        ``(cols, lens)`` with ``cols`` of shape ``(B, width)`` holding each
        packet's flow-feature lane per model input column (``-1`` = unused
        column, encoded as a zero code) and ``lens`` the declared feature
        counts.  Ids with no installed spec use the identity mapping over
        the first ``min(N_FLOW_FEATURES, width)`` lanes."""
        mids = np.asarray(model_ids, np.int64).reshape(-1)
        with self._lock:
            cache = self._spec_read_cache
            if cache is None or cache[0] != self._version:
                ident = np.full((1, self.max_width), -1, np.int32)
                k = min(N_FLOW_FEATURES, self.max_width)
                ident[0, :k] = np.arange(k, dtype=np.int32)
                cache = (self._version, self._spec_map,
                         np.concatenate([ident, self._spec_rows]),
                         np.concatenate([np.asarray([k], np.int32),
                                         self._spec_lens]))
                self._spec_read_cache = cache
        _, smap, rows_ext, lens_ext = cache
        slot = smap[mids] + 1  # 0 = the identity row
        w = min(width, rows_ext.shape[1])
        cols = rows_ext[slot][:, :w]
        if w < width:
            cols = np.concatenate(
                [cols, np.full((mids.shape[0], width - w), -1, np.int32)],
                axis=1)
        return cols, np.minimum(lens_ext[slot], width)

    # -- latency-SLO family ---------------------------------------------

    @staticmethod
    def _check_slo(budget_us) -> Optional[float]:
        """Validate an SLO budget before any table state is touched (the
        all-or-nothing install contract extends to the budget that rides
        along)."""
        if budget_us is None:
            return None
        b = float(budget_us)
        if not (b > 0.0 and np.isfinite(b)):
            raise ValueError(
                f"slo_budget_us must be a positive finite microsecond "
                f"count, got {budget_us!r}")
        return b

    def _prep_slo(self, model_id: int, slo: Optional[float]):
        """Copy-on-write budget row for an install's prepare block (caller
        holds the lock; None when no budget rides this install)."""
        if slo is None:
            return None
        slo_us = self._slo_us.copy()
        slo_us[int(model_id)] = slo
        return slo_us

    def _commit_slo(self, model_id: int, slo, slo_us) -> None:
        if slo_us is None:
            return
        self._slo_us = slo_us
        self._slo_models[int(model_id)] = slo
        self._slo_any = True

    def install_slo_budget(self, model_id: int, budget_us: float) -> None:
        """Install (or hot-swap) ``model_id``'s latency budget in
        microseconds — a per-model table family under the same generation
        swap (prepare-then-commit, crash-safe).  The ingress deadline
        scheduler reads it per packet at staging time and ships a short
        batch rather than let the oldest packet's remaining budget drop
        below the measured dispatch cost.  Like a feature spec, the budget
        belongs to the Model ID: it may be installed before the model and
        it survives ``remove()`` of the model."""
        slo = self._check_slo(budget_us)
        if slo is None:
            raise ValueError(
                "budget_us is required (remove_slo_budget() clears one)")
        if not 0 <= int(model_id) < 65536:
            raise ValueError(f"model id {model_id} outside the 16-bit "
                             "Model ID field")
        with self._lock:
            slo_us = self._prep_slo(model_id, slo)
            self._fire_fault("install")
            # -- commit (atomic under the lock) --
            self._commit_slo(model_id, slo, slo_us)
            self._version += 1
            self._emit("install_slo", model_id, budget_us=slo)

    def remove_slo_budget(self, model_id: int) -> None:
        """Clear a model's latency budget (no-op if none installed)."""
        with self._lock:
            if self._slo_models.pop(int(model_id), None) is None:
                return
            self._slo_us = self._slo_us.copy()
            self._slo_us[int(model_id)] = np.inf
            self._version += 1
            self._emit("remove", model_id, family="slo")

    def slo_budget(self, model_id: int) -> float:
        """This model's latency budget in µs (inf when none installed)."""
        with self._lock:
            return float(self._slo_us[int(model_id) & 0xFFFF])

    def slo_budget_rows(self, model_ids: np.ndarray) -> np.ndarray:
        """Vectorized per-packet budget gather (µs, float64; inf = no
        budget).  Copy-on-write publishes make the grabbed array an
        immutable snapshot, so the gather itself runs outside the lock."""
        with self._lock:
            slo = self._slo_us
        return slo[np.asarray(model_ids, np.int64).reshape(-1)]

    @property
    def slo_active(self) -> bool:
        """True once any latency budget has ever been installed (monotone
        — the ingress deadline scheduler's cheap per-batch gate)."""
        return self._slo_any

    # -- reflex family ---------------------------------------------------

    def install_reflex(self, model_id: int, program) -> int:
        """Install (or hot-swap) ``model_id``'s reflex program — a tiny
        vectorized threshold/vote rule (:class:`repro.serve.ReflexProgram`)
        that answers on the host in microseconds when the model lane would
        blow the packet's budget.  Packed into dense padded arrays under
        the same prepare-then-commit generation swap as every table
        family; returns the reflex slot.

        The program is duck-read (``lanes``/``thresholds``/``weights``/
        ``bias``/``on_true``/``on_false``) so core stays import-free of
        the serve layer."""
        lanes = np.asarray(program.lanes, np.int64).reshape(-1)
        thr = np.asarray(program.thresholds, np.int64).reshape(-1)
        wts = np.asarray(program.weights, np.int64).reshape(-1)
        bias = int(getattr(program, "bias", 0))
        on_true = np.asarray(program.on_true, np.int64).reshape(-1)
        on_false = np.asarray(program.on_false, np.int64).reshape(-1)
        if lanes.size == 0 or not (lanes.size == thr.size == wts.size):
            raise ValueError("reflex program needs equal-length, non-empty "
                             "lanes/thresholds/weights")
        if lanes.size > self.max_width:
            raise ValueError(f"reflex program has {lanes.size} terms > "
                             f"max_width={self.max_width}")
        if int(lanes.min()) < 0 or int(lanes.max()) >= self.max_width:
            raise ValueError(
                f"reflex lane outside [0, max_width={self.max_width})")
        if on_true.size == 0 or on_true.size != on_false.size \
                or on_true.size > self.max_width:
            raise ValueError("reflex output rows must be equal length in "
                             f"[1, max_width={self.max_width}]")
        i32 = np.iinfo(np.int32)
        for name, a in (("thresholds", thr), ("weights", wts),
                        ("on_true", on_true), ("on_false", on_false)):
            if int(a.min()) < i32.min or int(a.max()) > i32.max:
                raise ValueError(f"reflex {name} outside int32 code range")
        if not 0 <= int(model_id) < 65536:
            raise ValueError(f"model id {model_id} outside the 16-bit "
                             "Model ID field")
        with self._lock:
            # prepare-then-commit (same crash-safety contract as install())
            rmap = self._rx_map
            lane_t, thr_t = self._rx_lane.copy(), self._rx_thr.copy()
            w_t, bias_t = self._rx_w.copy(), self._rx_bias.copy()
            true_t, false_t = self._rx_true.copy(), self._rx_false.copy()
            od_t = self._rx_out_dim.copy()
            slot = int(rmap[model_id])
            if slot < 0:
                rmap = rmap.copy()
                slot = lane_t.shape[0]

                def _grow(a, fill=0):
                    pad = np.full((1,) + a.shape[1:], fill, a.dtype)
                    return np.concatenate([a, pad])
                lane_t, thr_t, w_t = _grow(lane_t), _grow(thr_t), _grow(w_t)
                bias_t = _grow(bias_t)
                true_t, false_t = _grow(true_t), _grow(false_t)
                od_t = _grow(od_t)
                rmap[model_id] = slot
            k, d = lanes.size, on_true.size
            # padding terms carry weight 0, so they never vote
            lane_t[slot] = 0
            thr_t[slot] = i32.max
            w_t[slot] = 0
            lane_t[slot, :k], thr_t[slot, :k], w_t[slot, :k] = lanes, thr, wts
            bias_t[slot] = bias
            true_t[slot] = 0
            false_t[slot] = 0
            true_t[slot, :d], false_t[slot, :d] = on_true, on_false
            od_t[slot] = d
            self._fire_fault("install")
            # -- commit (atomic under the lock) --
            self._rx_map = rmap
            self._rx_lane, self._rx_thr, self._rx_w = lane_t, thr_t, w_t
            self._rx_bias = bias_t
            self._rx_true, self._rx_false = true_t, false_t
            self._rx_out_dim = od_t
            self._rx_programs[int(model_id)] = program
            self._rx_any = True
            self._version += 1
            self._emit("install_reflex", model_id, slot=slot)
            return slot

    def remove_reflex(self, model_id: int) -> None:
        """Uninstall a reflex program; the model id falls back to the
        model-lane-only path (no-op if none installed)."""
        with self._lock:
            if self._rx_programs.pop(int(model_id), None) is None:
                return
            self._rx_map = self._rx_map.copy()
            self._rx_map[int(model_id)] = -1  # slot retired (programs tiny)
            self._version += 1
            self._emit("remove", model_id, family="reflex")

    def reflex_program(self, model_id: int):
        with self._lock:
            return self._rx_programs.get(int(model_id))

    def reflex_mask(self, model_ids: np.ndarray) -> np.ndarray:
        """Vectorized: True where a Model ID has a reflex program (the
        watermark controller's "can this packet take the reflex lane"
        check)."""
        with self._lock:
            rmap = self._rx_map
        return rmap[np.asarray(model_ids, np.int64).reshape(-1)] >= 0

    def reflex_evaluate(self, model_ids: np.ndarray, x0: np.ndarray
                        ) -> Tuple[np.ndarray, np.ndarray]:
        """Vectorized reflex-lane evaluation.  For each packet whose Model
        ID has a program: ``votes = bias + Σ_k w_k·[x[lane_k] ≥ thr_k]``;
        the output code row is ``on_true`` when votes ≥ 0 else
        ``on_false``.  Returns ``(mask, out)`` with ``out`` of shape
        ``(B, max_width)`` int32 (zero rows where ``mask`` is False).
        Pure host numpy — microseconds per batch, never touches the
        device, and the per-generation read cache makes the steady-state
        cost one map gather plus the term math."""
        mids = np.asarray(model_ids, np.int64).reshape(-1)
        with self._lock:
            cache = self._rx_read_cache
            if cache is None or cache[0] != self._version:
                cache = (self._version, self._rx_map, self._rx_lane,
                         self._rx_thr, self._rx_w, self._rx_bias,
                         self._rx_true, self._rx_false)
                self._rx_read_cache = cache
        _, rmap, lane, thr, w, bias, tr, fl = cache
        slot = rmap[mids]
        mask = slot >= 0
        out = np.zeros((mids.size, self.max_width), np.int32)
        if not mask.any():
            return mask, out
        s = slot[mask]
        x = np.asarray(x0)[mask]
        # lanes are validated < max_width at install; a narrower serving
        # width clamps (clamped padding terms carry weight 0 regardless)
        idx = np.minimum(lane[s], x.shape[1] - 1)
        terms = (np.take_along_axis(x, idx, axis=1) >= thr[s])
        votes = bias[s] + np.einsum("bk,bk->b", w[s].astype(np.int64),
                                    terms.astype(np.int64))
        out[mask] = np.where((votes >= 0)[:, None], tr[s], fl[s])
        return mask, out

    @property
    def reflex_active(self) -> bool:
        """True once any reflex program has ever been installed (monotone
        — the ingress watermark controller's cheap gate)."""
        return self._rx_any

    @property
    def forest_active(self) -> bool:
        """True once any forest has ever been installed (monotone — the
        engine's static forest-lane switch keys off this, so it can flip at
        most once per process)."""
        return self._forest_ever

    @staticmethod
    def _uploader(device):
        """Host→device array upload for one snapshot: ``jnp.asarray`` when
        no placement is requested (the N=1 path — uncommitted, lands on the
        default device exactly as before), else a committed
        ``jax.device_put`` so a sharded engine's whole dispatch follows its
        tables onto its own device."""
        if device is None:
            return jnp.asarray
        return lambda a: jax.device_put(a, device)

    def forest_tables(self, device=None) -> ForestTables:
        """Device snapshot of the forest table generation — same caching
        and double-buffer read semantics as :meth:`tables`.  Keyed on the
        forest family's own write counter, so MLP hot-swaps never re-upload
        the unchanged forest tables (and vice versa)."""
        with self._lock:
            return self._forest_tables_locked(device)

    def _forest_tables_locked(self, device=None) -> ForestTables:
        snap = self._forest_snapshot.get(device)
        if snap is None or snap[0] != self._forest_gen:
            put = self._uploader(device)
            snap = (self._forest_gen, ForestTables(
                nodes=put(self._f_nodes),
                tree_on=put(self._f_tree_on),
                mode=put(self._f_mode),
                out_dim=put(self._f_out_dim),
                id_map=put(self._f_id_map),
            ))
            self._forest_snapshot[device] = snap
        return snap[1]

    def range_tables(self, device=None) -> RangeTables:
        """Device snapshot of the range-table lowering of the forest family
        — same caching and double-buffer read semantics as
        :meth:`forest_tables`, keyed on the same forest write counter (the
        two lowerings publish together, by construction)."""
        if not self.range_available:
            raise RuntimeError(
                f"range tables unavailable: max_nodes={self.max_nodes} "
                "exceeds the 32-leaf mask bound (needs max_nodes <= 64)")
        with self._lock:
            return self._range_tables_locked(device)

    def _range_tables_locked(self, device=None) -> RangeTables:
        snap = self._range_snapshot.get(device)
        if snap is None or snap[0] != self._forest_gen:
            put = self._uploader(device)
            snap = (self._forest_gen, RangeTables(
                feat=put(self._r_feat),
                thresh=put(self._r_th),
                lmask=put(self._r_mask),
                payload=put(self._r_payload),
            ))
            self._range_snapshot[device] = snap
        return snap[1]

    def forest_snapshots(self, want_ranges: bool, device=None
                         ) -> Tuple[ForestTables, Optional[RangeTables]]:
        """One-lock read of BOTH forest lowerings from the **same**
        generation.  Readers that mix fields across the two pytrees (the
        range traversal takes tree liveness/mode/id_map from
        :class:`ForestTables` and its range rows from :class:`RangeTables`)
        must use this instead of two separate calls: an ``install_forest``
        landing between two lock acquisitions would otherwise hand them a
        torn pair — e.g. generation-N ``tree_on`` marking trees live whose
        generation-N+1 range rows are already padding, which votes garbage
        rather than serving stale-but-consistent results."""
        with self._lock:
            ftables = self._forest_tables_locked(device)
            rtables = (self._range_tables_locked(device) if want_ranges
                       else None)
            return ftables, rtables

    # -- data-plane reads -------------------------------------------------

    def tables(self, device=None) -> ModelTables:
        """Device snapshot of the current table generation.

        The snapshot is cached until the next write bumps the generation, so
        repeated batches feed the *same* device buffers to the jit'd data
        plane (no per-batch host→device upload) while an in-flight batch
        holding an older generation keeps its buffers alive — the
        double-buffer read side.  The arrays are traced arguments of the
        data plane, never captured constants, so a generation swap is just
        different buffers: zero retraces.

        ``device`` asks for a snapshot committed to that device (one cache
        entry per device): N engine shards reading one control plane each
        get their own resident copy of the same generation, uploaded lazily
        and only re-uploaded when a write bumps the family counter.
        """
        with self._lock:
            snap = self._snapshot.get(device)
            if snap is None or snap[0] != self._mlp_gen:
                put = self._uploader(device)
                snap = (self._mlp_gen, ModelTables(
                    w=put(self._w),
                    b=put(self._b),
                    act=put(self._act),
                    layer_on=put(self._layer_on),
                    out_dim=put(self._out_dim),
                    id_map=put(self._id_map),
                ))
                self._snapshot[device] = snap
            return snap[1]

    @property
    def version(self) -> int:
        """Table generation — bumped by every install/remove swap."""
        return self._version

    def table_bytes(self) -> int:
        n = (self._w.nbytes + self._b.nbytes + self._act.nbytes
             + self._layer_on.nbytes + self._out_dim.nbytes
             + self._id_map.nbytes + self._f_nodes.nbytes
             + self._f_tree_on.nbytes + self._f_mode.nbytes
             + self._f_out_dim.nbytes + self._f_id_map.nbytes)
        if self.range_available:
            n += (self._r_feat.nbytes + self._r_th.nbytes
                  + self._r_mask.nbytes + self._r_payload.nbytes)
        return n


class WeightRegistry:
    """LM-scale control plane: named parameter pytrees with hot-swap.

    ``serve.py`` jits its decode step over *abstract* parameters; installing
    a new checkpoint (same structure) swaps buffers without recompiling —
    the same property as :class:`ControlPlane`, at framework scale.
    """

    def __init__(self):
        self._models: Dict[str, object] = {}
        self._structs: Dict[str, jax.tree_util.PyTreeDef] = {}
        self._lock = threading.Lock()
        self.swaps = 0

    def install(self, name: str, params) -> None:
        with self._lock:
            leaves, treedef = jax.tree_util.tree_flatten(params)
            if name in self._structs and treedef != self._structs[name]:
                raise ValueError(
                    f"hot-swap for '{name}' changed parameter structure; "
                    "a structure change is a data-plane re-synthesis")
            self._models[name] = params
            self._structs[name] = treedef
            self.swaps += 1

    def get(self, name: str):
        with self._lock:
            return self._models[name]

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._models)
