"""Fused per-flow register update + feature emit (the stateful stage a P4
SmartNIC computes in register externs before the ML stage).

The flow engine (``repro.flow``) resolves each raw packet's 5-tuple to a
flow-table slot on the host; this stage then performs, for a fixed-shape
batch of parsed headers, the whole **stateful** update in one pass:

    for each packet p (batch order):
        row        = registers[slot[p]]          # dynamic row gather
        row'       = update(row, ts[p], len[p])  # counters, EWMAs, min/max
        registers[slot[p]] = row'                # dynamic row scatter
        cms[d, cell[p,d]] += 1  (∀d)             # count-min heavy-hitter lane
        features[p] = emit(row', cms)            # post-update codes at frac

Batch order matters: two packets of one flow in the same batch chain their
EWMAs, exactly like back-to-back packets through a hardware register ALU.
That makes the update a *sequential scatter* — the one stage of this repo's
data plane that is not embarrassingly batch-parallel.  It runs on the host
on every platform: the register file lives next to the flow hash table,
which owns eviction, and shard failover migrates register rows straight
from it.

:func:`flow_update_gather` is the lowering: packets are ranked within their
flow (stable batch order), and rank-``r`` packets across *distinct* flows
update in one vectorized numpy round — the sequential chain only costs
rounds = max packets-per-flow-per-batch, not B.  The count-min lane needs
no rounds at all: increments commute, so each packet's post-update estimate
has the closed form ``min(prior + rank_in_cell + 1, FLOW_CODE_MAX)``.

It is bit-exact against the pure-Python per-packet oracle
``ref.flow_update_numpy`` (asserted by hypothesis property tests) — same
contract discipline as the MLP and forest kernels.
"""

from __future__ import annotations

import numpy as np

from .ref import (FLOW_CODE_MAX, N_FLOW_FEATURES, N_FLOW_REGISTERS,
                  REG_BYTE_COUNT, REG_EWMA_IAT, REG_EWMA_LEN, REG_FIRST_TS,
                  REG_LAST_TS, REG_MAX_LEN, REG_MIN_LEN, REG_PKT_COUNT,
                  rounding_rshift_np, sat_shl_np)

__all__ = ["flow_update_gather", "rank_from_order", "cms_estimate_update"]


def rank_from_order(order: np.ndarray, newg: np.ndarray) -> np.ndarray:
    """Per-group occurrence rank (original order) from a stable sort's
    ``order`` permutation and its group-start mask ``newg`` — THE rank
    definition, shared with ``core.ingress._dedup_rows(want_rank=True)``
    so the flow table's dedup by-product and this module's own fallback can
    never drift apart."""
    n = order.shape[0]
    ar = np.arange(n)
    gstart = np.maximum.accumulate(np.where(newg, ar, 0))
    rank = np.empty(n, np.int64)
    rank[order] = ar - gstart
    return rank


def _rank_within_groups(keys: np.ndarray, key_bound: int = 1 << 62):
    """Stable per-key rank: the k-th occurrence of a key (in array order)
    gets rank k.  One scalar argsort — the same trick as the ingress dedup.
    Numpy's stable sort radixes by key *bytes*, so when the caller knows
    the keys fit a narrower int (``key_bound``), sorting the downcast keys
    is up to 4× faster — the rank only needs the grouping, and a lossless
    downcast preserves it exactly."""
    n = keys.shape[0]
    if key_bound <= 1 << 15:
        sort_keys = keys.astype(np.int16, copy=False)
    else:
        sort_keys = keys.astype(np.int32, copy=False)
    order = np.argsort(sort_keys, kind="stable")
    sk = keys[order]
    newg = np.empty(n, bool)
    newg[0] = True
    newg[1:] = sk[1:] != sk[:-1]
    return rank_from_order(order, newg)


def cms_estimate_update(cms: np.ndarray, cells: np.ndarray) -> np.ndarray:
    """Count-min lane closed form, shared by the vectorized lowering below
    and the sharded fabric's *global* sketch: increments commute, so the
    post-update estimate each packet observes is
    ``min(prior + rank_in_cell + 1, FLOW_CODE_MAX)`` — no sequential
    rounds — and the cell totals fold in as one saturating bincount per
    sketch row.  Updates ``cms`` **in place** (int32 ``(D, Wc)``) and
    returns the per-packet estimates (int32 ``(B,)``, pre-quantization).

    One definition on purpose: the fabric computes this over the whole
    arrival batch (every shard's packets, original order) against one
    shared sketch, which is exactly what the N=1 path computes — so the
    sharded CMS feature is bit-exact with single-shard serving by
    construction, not by parallel reimplementation.
    """
    cl = np.asarray(cells, np.int64).reshape(cells.shape[0], -1)
    code_max = np.int32(FLOW_CODE_MAX)
    est = np.full(cl.shape[0], FLOW_CODE_MAX, np.int32)
    if cl.shape[0] == 0:
        return est
    for d in range(cms.shape[0]):
        cd = cl[:, d]
        prior = cms[d, cd]
        est_d = np.minimum(prior + (_rank_within_groups(cd, cms.shape[1])
                                    + 1).astype(np.int32), code_max)
        est = np.minimum(est, est_d)
        counts = np.bincount(cd, minlength=cms.shape[1])
        np.minimum(cms[d] + counts.astype(np.int32), code_max,
                   out=cms[d])
    return est


def flow_update_gather(state: np.ndarray, cms: np.ndarray, slots: np.ndarray,
                       cells: np.ndarray, ts: np.ndarray, length: np.ndarray,
                       live: np.ndarray, *, frac: int, ewma_shift: int,
                       byte_shift: int, dur_shift: int, copy: bool = True,
                       rank: "np.ndarray | None" = None):
    """Bit-identical host realization: rank-round vectorized scatter.

    Packets are ranked within their flow (stable batch order); round ``r``
    updates every flow's rank-``r`` packet at once — all distinct slots, so
    the scatter is race-free and the EWMA chains stay in exact batch order.
    Wall-clock scales with *max packets per flow per batch*, not batch size:
    a 2048-packet batch over hundreds of concurrent flows runs in a handful
    of vectorized rounds.

    ``copy=False`` updates ``state``/``cms`` in place (the serving hot path:
    the flow table's register file is megabytes, and re-copying it per batch
    would dwarf the update itself).

    All arithmetic is int32: exact as long as the
    inputs respect the wire's field ranges — ``ts`` non-negative int32 and
    every register/length within ``[0, FLOW_CODE_MAX]`` (lengths are
    clamped on entry; the update itself can then never leave the range —
    the same invariant the oracle's saturation bounds establish).
    """
    state = np.array(state, np.int32, copy=True) if copy \
        else np.asarray(state)
    cms = np.array(cms, np.int32, copy=True) if copy else np.asarray(cms)
    slots = np.asarray(slots, np.int64).reshape(-1)
    ts = np.asarray(ts, np.int32).reshape(-1)
    length = np.minimum(
        np.maximum(np.asarray(length, np.int32).reshape(-1), 0),
        FLOW_CODE_MAX)
    n = slots.shape[0]
    code_max = np.int32(FLOW_CODE_MAX)
    feats = np.zeros((n, N_FLOW_FEATURES), np.int32)
    live = np.asarray(live).reshape(-1).astype(bool)
    idx = None if live.all() else np.nonzero(live)[0]
    if n == 0 or (idx is not None and idx.size == 0):
        return state, cms, feats
    lslots = slots if idx is None else slots[idx]

    len_q_all = sat_shl_np(length, frac)  # hoisted: round-invariant
    if rank is None:  # callers holding a flow-table rank pass it through
        rank = _rank_within_groups(lslots, state.shape[0])
    else:
        rank = np.asarray(rank).reshape(-1)
        if idx is not None:
            rank = rank[idx]
    rounds = int(rank.max()) + 1
    for r in range(rounds):
        lsel = np.nonzero(rank == r)[0] if rounds > 1 \
            else np.arange(lslots.shape[0])
        sel = lsel if idx is None else idx[lsel]
        s = slots[sel]  # one packet per flow → race-free scatter
        t = ts[sel]
        ln = length[sel]
        row = state[s]
        cnt = row[:, REG_PKT_COUNT]
        len_q = len_q_all[sel]
        iat_q = sat_shl_np(np.maximum(t - row[:, REG_LAST_TS], 0), frac)
        blend_iat = row[:, REG_EWMA_IAT] + rounding_rshift_np(
            iat_q - row[:, REG_EWMA_IAT], ewma_shift)
        blend_len = row[:, REG_EWMA_LEN] + rounding_rshift_np(
            len_q - row[:, REG_EWMA_LEN], ewma_shift)
        if (cnt > 1).all():
            # steady fast path: every flow mid-stream — the branch selects
            # below collapse to their blend/accumulate arms
            iat_e = blend_iat
            len_e = blend_len
            mn = np.minimum(row[:, REG_MIN_LEN], ln)
            mx = np.maximum(row[:, REG_MAX_LEN], ln)
            byte = np.minimum(row[:, REG_BYTE_COUNT] + ln, code_max)
            cnt2 = np.minimum(cnt + 1, code_max)
            first = row[:, REG_FIRST_TS]
        else:
            fresh = cnt == 0
            iat_e = np.where(fresh, 0,
                             np.where(cnt == 1, iat_q, blend_iat))
            len_e = np.where(fresh, len_q, blend_len)
            mn = np.where(fresh, ln, np.minimum(row[:, REG_MIN_LEN], ln))
            mx = np.where(fresh, ln, np.maximum(row[:, REG_MAX_LEN], ln))
            byte = np.where(fresh, np.minimum(ln, code_max),
                            np.minimum(row[:, REG_BYTE_COUNT] + ln,
                                       code_max))
            cnt2 = np.where(fresh, np.int32(1),
                            np.minimum(cnt + 1, code_max))
            first = np.where(fresh, t, row[:, REG_FIRST_TS])
        new_row = np.empty((s.shape[0], N_FLOW_REGISTERS), np.int32)
        for col, v in ((REG_PKT_COUNT, cnt2), (REG_BYTE_COUNT, byte),
                       (REG_LAST_TS, t), (REG_FIRST_TS, first),
                       (REG_EWMA_IAT, iat_e), (REG_EWMA_LEN, len_e),
                       (REG_MIN_LEN, mn), (REG_MAX_LEN, mx)):
            new_row[:, col] = v
        state[s] = new_row
        block = np.empty((s.shape[0], N_FLOW_FEATURES - 1), np.int32)
        block[:, 0] = sat_shl_np(cnt2, frac)
        block[:, 1] = sat_shl_np(byte >> byte_shift, frac)
        block[:, 2] = iat_e
        block[:, 3] = len_e
        block[:, 4] = sat_shl_np(mn, frac)
        block[:, 5] = sat_shl_np(mx, frac)
        block[:, 6] = sat_shl_np(
            np.maximum(t - first, 0) >> dur_shift, frac)
        feats[sel, : N_FLOW_FEATURES - 1] = block[:, : N_FLOW_FEATURES - 1]

    # count-min lane: the shared closed form (see cms_estimate_update)
    cl = np.asarray(cells, np.int64).reshape(n, -1)
    if idx is not None:
        cl = cl[idx]
    est = cms_estimate_update(cms, cl)
    cms_q = sat_shl_np(est, frac)
    if idx is None:
        feats[:, N_FLOW_FEATURES - 1] = cms_q
    else:
        feats[idx, N_FLOW_FEATURES - 1] = cms_q
    return state, cms, feats
