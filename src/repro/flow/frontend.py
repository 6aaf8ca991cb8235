"""Flow frontend: raw 5-tuple headers → per-flow features → the serving
pipeline.

This is the stage the paper's pipeline gets from P4 stateful externs and we
previously skipped: real traffic has no feature vectors, it has packets.
``submit_raw()`` closes that gap —

    raw header batch ──▶ parse (numpy)                     data/packets.py
        │
        ▼
    FlowTable.lookup_or_insert        5-tuple → register slot (open
        │                             addressing, idle expiry, eviction)
        ▼
    kernels.flow_update               sequential scatter-update of the
        │                             register file + count-min sketch
        │                             (host rank-round lowering), emits
        │                             post-update feature codes
        ▼
    FeatureSpec gather                per-packet: which flow-feature lanes
        │                             feed this Model ID's input columns
        ▼
    IngressPipeline.submit_features()   (dedup → cache → lane-pure fused
                                         dispatch; wire bytes only at egress)

Everything upstream of the pipeline is host-side vectorized numpy (the
registers live next to the flow hash table), so a FeatureSpec reinstall —
re-mapping which registers feed which model — is a pure control-plane
swap: zero data-plane retraces by construction.  It stays on the host on
every platform: shard failover migrates register rows straight out of the
table, so a wedged device never holds the only copy of flow state.

Converged flows are where this design pays: a periodic/telemetry flow's
EWMA registers reach a fixed point, its feature rows byte-repeat, and the
ingress result cache short-circuits the entire device trip — the
"aggregation, not FLOPs" regime pForest/Planter describe, now reproduced
from raw packets.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

from ..core.ingress import _dedup_rows
from ..data.packets import RAW_KEY_BYTES, RawHeaderBatch, parse_raw_headers
from ..kernels.ops import flow_update
from ..kernels.ref import N_FLOW_FEATURES, flow_update_numpy
from .table import FlowTable

__all__ = ["FlowParams", "FlowFrontend", "reference_features"]

# Deterministic odd multipliers, one per count-min sketch row (the sketch's
# pairwise-independent-ish hash family over the 64-bit key hash).
_CMS_MULTS = ((np.random.default_rng(0x51E7C4).integers(
    0, 2 ** 63, 8, np.uint64) << np.uint64(1)) | np.uint64(1))


@dataclasses.dataclass(frozen=True)
class FlowParams:
    """Flow-engine arithmetic configuration (shared by the frontend, the
    kernels and the reference oracle — one source of truth so bit-exact
    comparisons can never drift on config).

    ``frac`` is the wire's fixed-point grid (``ControlPlane.frac_bits``);
    ``ewma_shift`` the EWMA alpha as a right shift (alpha = 2^-shift);
    ``byte_shift``/``dur_shift`` pre-scale byte counts / durations before
    they are encoded (they grow far faster than per-packet quantities);
    ``cms_depth``×``2**cms_width_pow2`` is the count-min sketch geometry.
    """

    frac: int
    ewma_shift: int = 3
    byte_shift: int = 6
    dur_shift: int = 10
    cms_depth: int = 2
    cms_width_pow2: int = 12

    def __post_init__(self):
        if not 0 < self.cms_depth <= _CMS_MULTS.size:
            raise ValueError(f"cms_depth outside (0, {_CMS_MULTS.size}]")
        if not 0 < self.cms_width_pow2 < 31:
            raise ValueError("cms_width_pow2 outside (0, 31)")

    def cms_cells(self, hashes: np.ndarray) -> np.ndarray:
        """Per-row sketch cells from the 64-bit key hashes (uint64 multiply
        wraps, top bits select the cell)."""
        mults = _CMS_MULTS[: self.cms_depth]
        return ((hashes[:, None] * mults[None, :])
                >> np.uint64(64 - self.cms_width_pow2)).astype(np.int32)


class FlowFrontend:
    """Stateful flow engine in front of an
    :class:`~repro.core.ingress.IngressPipeline`.

    Parameters
    ----------
    pipeline:
        The serving pipeline; its control plane supplies the wire grid
        (``frac_bits``) and the per-model :class:`FeatureSpec` mappings.
    capacity_pow2 / idle_timeout:
        Flow-table geometry and aging (see :class:`FlowTable`).
    params:
        :class:`FlowParams` override (default derives from the control
        plane's ``frac_bits``).
    """

    def __init__(self, pipeline, *, capacity_pow2: int = 14,
                 idle_timeout: Optional[int] = None,
                 params: Optional[FlowParams] = None):
        self.pipeline = pipeline
        self.cp = pipeline.cp
        self.engine = pipeline.engine
        self.params = params or FlowParams(frac=self.cp.frac_bits)
        self.width = self.engine.max_features  # wire feature-block columns
        self.key_words = (RAW_KEY_BYTES + 7) // 8
        self.table = FlowTable(self.key_words, capacity_pow2=capacity_pow2,
                               idle_timeout=idle_timeout)
        # layer spans under the pipeline's shard label
        self.span = pipeline.span
        self.table.span = self.span
        self.cms = np.zeros(
            (self.params.cms_depth, 1 << self.params.cms_width_pow2),
            np.int32)
        # canonical names (see FlowTable.stats); the frontend's cells
        # graft into the owning server's registry along with the table's,
        # plus a flow_occupancy gauge collector
        from ..obs import Counter, StatsAdapter
        stats = StatsAdapter()
        stats.bind("flow_raw_packets_total", Counter())
        stats.bind("flow_raw_batches_total", Counter())
        self.stats = stats
        self._arange = np.arange(0).reshape(0, 1)  # grown on demand
        self._ones = np.ones(0, np.int32)

    # -- feature extraction -------------------------------------------------

    def extract(self, raw, *, fields: Optional[RawHeaderBatch] = None,
                cms_est_q: Optional[np.ndarray] = None
                ) -> Tuple[np.ndarray, RawHeaderBatch, np.ndarray,
                           np.ndarray]:
        """Run the stateful stage for one raw header batch: resolve flows,
        update registers/sketch, emit features.  Returns ``(features,
        fields, is_new, rejected)`` with ``features`` (B, N_FLOW_FEATURES)
        int32 codes at ``params.frac`` (post-update state as each packet
        observed it) and ``rejected`` True where the flow table overflowed
        and rejected the packet's whole flow (its feature row is zeros and
        must not be served — ``submit_raw`` turns it into a per-packet
        error slot; rejected flows never touch register or sketch state).

        ``fields`` lets a caller that already parsed the headers (the
        sharded fabric's dispatcher hashes the 5-tuples before routing)
        skip the second parse; ``cms_est_q`` overrides the count-min
        feature lane with externally computed codes — the fabric maintains
        ONE global sketch across shards (heavy-hitter counts are a
        whole-fabric property; a per-shard sketch would see only its own
        flows and diverge from the N=1 estimates whenever flows on
        different shards collide in a cell), so each shard's private
        sketch becomes scratch and the global per-packet estimates ride in
        through this override.
        """
        span = self.span
        with span("flow.parse"):
            if fields is None:
                fields = parse_raw_headers(raw)
            n = fields.model_id.shape[0]
            if n == 0:
                return (np.zeros((0, N_FLOW_FEATURES), np.int32), fields,
                        np.zeros(0, bool), np.zeros(0, bool))
            words, hashes = FlowTable.pack_keys(fields.key_bytes,
                                                self.key_words)
        self.stats["flow_raw_packets_total"] += n
        self.stats["flow_raw_batches_total"] += 1
        with span("flow.lookup"):
            slots, is_new, rank = self.table.lookup_or_insert(
                words, hashes, fields.ts, want_rank=True)
        with span("flow.update"):
            feats = self._update(fields, hashes, slots, rank)
        if cms_est_q is not None:
            feats[:, N_FLOW_FEATURES - 1] = cms_est_q
        return feats, fields, is_new, slots < 0

    def _update(self, fields: RawHeaderBatch, hashes: np.ndarray,
                slots: np.ndarray, rank: Optional[np.ndarray]) -> np.ndarray:
        """Sketch cells, then the register and sketch update in place;
        returns the post-update feature codes (zero rows where the table
        rejected the flow)."""
        n = slots.shape[0]
        rejected = slots < 0
        cells = self.params.cms_cells(hashes)
        p = self.params
        if self._ones.shape[0] < n:
            self._ones = np.ones(n, np.int32)
        # registers and sketch update in place (copy=False)
        if rejected.any():
            # overflow degradation: whole flows were rejected, so the kept
            # packets' slots and within-flow ranks are still exact — run
            # the update kernel on the kept subset and leave zero rows
            # (never served) at the rejected positions
            keep = np.nonzero(~rejected)[0]
            feats = np.zeros((n, N_FLOW_FEATURES), np.int32)
            if keep.size:
                feats[keep] = flow_update(
                    self.table.registers, self.cms, slots[keep],
                    cells[keep], fields.ts[keep], fields.length[keep],
                    self._ones[: keep.size], frac=p.frac,
                    ewma_shift=p.ewma_shift, byte_shift=p.byte_shift,
                    dur_shift=p.dur_shift, copy=False,
                    rank=None if rank is None else rank[keep])[2]
        else:
            feats = flow_update(
                self.table.registers, self.cms, slots, cells, fields.ts,
                fields.length, self._ones[:n], frac=p.frac,
                ewma_shift=p.ewma_shift, byte_shift=p.byte_shift,
                dur_shift=p.dur_shift, copy=False, rank=rank)[2]
        return feats

    # -- serving -------------------------------------------------------------

    def _gather(self, feats: np.ndarray, model_id: np.ndarray) -> np.ndarray:
        """Per-model FeatureSpec gather: land each packet's flow-feature
        lanes on its model's input columns (one int32 gather — ``-1``
        columns read the appended zero lane)."""
        with self.span("flow.gather"):
            n = feats.shape[0]
            cols, _ = self.cp.feature_spec_rows(model_id, self.width)
            feats_z = np.concatenate(
                [feats, np.zeros((n, 1), np.int32)], axis=1)
            if self._arange.shape[0] < n:
                self._arange = np.arange(n).reshape(n, 1)
            return np.ascontiguousarray(feats_z[self._arange[:n], cols])

    def submit_raw(self, raw, *, fields: Optional[RawHeaderBatch] = None,
                   cms_est_q: Optional[np.ndarray] = None,
                   drop_mask: Optional[np.ndarray] = None,
                   drop_reason: str = "malformed raw header"
                   ) -> Tuple[int, int]:
        """Feed one raw header batch through flow-update → feature-spec
        gather → the ingress pipeline's **feature-domain** entry.  Returns
        the pipeline's ``(first_ticket, n_packets)``; results arrive
        through the usual ``drain()`` surface in submission order.
        ``fields``/``cms_est_q`` pass through to :meth:`extract` (the
        sharded fabric's pre-parsed, global-sketch entry).

        ``drop_mask`` marks rows the caller's validation already rejected
        (truncated/malformed headers): they never touch flow state and
        resolve as :class:`~repro.core.ingress.PacketError` slots carrying
        ``drop_reason``, interleaved at their submission-order positions.
        Flow-table overflow rejections from :meth:`extract` degrade the
        same way (reason ``"flow table overflow"``).

        No wire rows are built on ingress any more: the spec gather lands
        each packet's flow-feature lanes on its model's input columns and
        the parsed features go straight to
        ``IngressPipeline.submit_features`` (dedup → cache → lane-pure
        fused dispatch).  The wire byte layout is paid once, at egress,
        when a retired batch's results are encoded — byte-identical to the
        old encapsulate→parse round trip (asserted by the tier-1 suite).
        """
        if drop_mask is not None and drop_mask.any():
            return self._submit_raw_partial(raw, fields, cms_est_q,
                                            np.asarray(drop_mask, bool),
                                            drop_reason)
        feats, fields, _, rejected = self.extract(raw, fields=fields,
                                                  cms_est_q=cms_est_q)
        n = feats.shape[0]
        if n == 0:
            return self.pipeline.submit_features(
                np.zeros((0, self.width), np.int32), np.zeros(0, np.int32))
        gathered = self._gather(feats, fields.model_id)
        if rejected.any():
            return self.pipeline.submit_features(
                gathered, fields.model_id, error_mask=rejected,
                error_reason="flow table overflow — flow rejected")
        return self.pipeline.submit_features(gathered, fields.model_id)

    def _submit_raw_partial(self, raw, fields, cms_est_q,
                            drop: np.ndarray, drop_reason: str
                            ) -> Tuple[int, int]:
        """Validation-rejected rows interleave as error tickets while the
        good subset runs the full flow stage (rejected rows must never
        touch register/sketch state)."""
        n_total = drop.size
        x_full = np.zeros((n_total, self.width), np.int32)
        mid_full = np.zeros(n_total, np.int32)
        err = drop.copy()
        reasons = np.full(n_total, drop_reason, object)
        good = np.nonzero(~drop)[0]
        if good.size:
            if fields is not None:
                sub_fields = RawHeaderBatch(
                    key_bytes=fields.key_bytes[good],
                    model_id=fields.model_id[good],
                    ts=fields.ts[good], length=fields.length[good])
                sub_raw = raw
            else:
                sub_fields = None
                sub_raw = np.ascontiguousarray(
                    np.asarray(raw), np.uint8)[good]
            sub_est = None if cms_est_q is None else cms_est_q[good]
            feats, f2, _, rejected = self.extract(
                sub_raw, fields=sub_fields, cms_est_q=sub_est)
            x_full[good] = self._gather(feats, f2.model_id)
            mid_full[good] = f2.model_id
            if rejected.any():
                gi = good[rejected]
                err[gi] = True
                reasons[gi] = "flow table overflow — flow rejected"
        return self.pipeline.submit_features(
            x_full, mid_full, error_mask=err, error_reason=reasons)

    # -- checkpoint / restore (live-migration surface) -----------------------

    def snapshot(self) -> dict:
        """Checkpoint the whole stateful stage: flow table (live keys +
        register rows + generation) and the count-min sketch — everything
        a failover needs to continue this frontend's flows bit-exact
        elsewhere."""
        return {"table": self.table.snapshot(), "cms": self.cms.copy()}

    def restore(self, snap: dict) -> None:
        """Restore a :meth:`snapshot` (table rebuild under a generation
        bump + sketch copy-in).  Geometry must match — a snapshot is a
        checkpoint, not a resize tool."""
        cms = np.asarray(snap["cms"], np.int32)
        if cms.shape != self.cms.shape:
            raise ValueError(
                f"snapshot sketch geometry {cms.shape} != this "
                f"frontend's {self.cms.shape}")
        self.table.restore(snap["table"])
        self.cms[:] = cms

    def flow_table_hit_rate(self) -> float:
        return self.table.hit_rate()


def reference_features(raw, params: FlowParams) -> np.ndarray:
    """Hand-built feature vectors for a raw trace: the pure-Python oracle
    over an unbounded flow table (every 5-tuple gets its own slot, no
    expiry/eviction).  This is the ground truth ``submit_raw()`` must
    reproduce bit-exactly whenever the real table never evicts — the
    end-to-end acceptance check for the whole flow engine."""
    fields = parse_raw_headers(raw)
    if fields.model_id.shape[0] == 0:
        return np.zeros((0, N_FLOW_FEATURES), np.int32)
    key_words = (RAW_KEY_BYTES + 7) // 8
    words, hashes = FlowTable.pack_keys(fields.key_bytes, key_words)
    uidx, inverse = _dedup_rows(words, hashes)  # flow id per packet
    from ..kernels.ref import N_FLOW_REGISTERS
    state = np.zeros((uidx.size, N_FLOW_REGISTERS), np.int32)
    cms = np.zeros((params.cms_depth, 1 << params.cms_width_pow2), np.int32)
    cells = params.cms_cells(hashes)
    _, _, feats = flow_update_numpy(
        state, cms, inverse, cells, fields.ts, fields.length,
        np.ones(inverse.shape[0], np.int32), frac=params.frac,
        ewma_shift=params.ewma_shift, byte_shift=params.byte_shift,
        dur_shift=params.dur_shift)
    return feats
