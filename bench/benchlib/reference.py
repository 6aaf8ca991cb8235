"""The plain reference: what every answer must be, bit for bit.

Written from the integer semantics the deployment states (its config
file's ``server`` and ``semantics``) and from the tenants' own integer
tables, which the benchmark draws from the seed before it installs them.
It imports nothing of the program and reads nothing the program made.

* flow registers: a per-packet walk of each sampled flow (packet and byte
  counters, rounding-shift EWMAs of inter-arrival and length, min/max
  length, duration), plus the count-min sketch estimate, which depends on
  every packet submitted before it;
* the FeatureSpec gather: flow lanes onto each tenant's input columns;
* the MLP lane: int32 wrap-around matvec, rounding right shift, opcode
  activations (ReLU, the Taylor sigmoid of paper Table 3, leaky ReLU,
  hard sigmoid), output lanes beyond the tenant's width zeroed;
* the forest lane: a level-bounded walk of the node tables, majority vote
  or summed leaves;
* the egress encode of paper Table 1 (outputs replace the feature block).
"""

from __future__ import annotations

import numpy as np

from .wire import (FLAG_RESULT, HEADER_BYTES, encode_wire, key_hashes,
                   odd_multipliers, raw_fields)

ACT_CODES = {"none": 0, "relu": 1, "sigmoid": 2, "leaky_relu": 3,
             "hard_sigmoid": 4}
FLOW_CODE_MAX = (1 << 30) - 1
CLASSIFY = 1


def rshift_round(x: np.ndarray, shift: int) -> np.ndarray:
    """Arithmetic right shift rounding to nearest, ties away from zero, in
    the operand's own integer width (int32 adds wrap)."""
    if shift <= 0:
        return x
    half = np.where(x >= 0, 1 << (shift - 1), (1 << (shift - 1)) - 1)
    return (x + half.astype(x.dtype)) >> shift


def sigmoid_constants(series, order: int, frac: int) -> list:
    """Taylor constants of the sigmoid at ``2**frac``, truncated toward
    zero (paper Table 4)."""
    return [int(c * (1 << frac)) for c in series[: order + 1]]


class Reference:
    """Expected egress of every sampled packet of one run."""

    def __init__(self, cfg: dict, tenants: list):
        srv, sem = cfg["server"], cfg["semantics"]
        self.frac = srv["frac_bits"]
        self.width = srv["max_width"]
        self.depth = srv["max_tree_depth"]
        self.sig = sigmoid_constants(sem["sigmoid_series"],
                                     srv["taylor_order"], self.frac)
        self.alpha_q = int(round(sem["leaky_alpha"] * (1 << self.frac)))
        self.flow = sem["flow"]
        self.tenants = {t["id"]: t for t in tenants}

    # -- flow engine --------------------------------------------------------

    def flow_features(self, stream: np.ndarray, pos: np.ndarray
                      ) -> np.ndarray:
        """Flow-feature codes ``(len(pos), 8)`` of the packets at stream
        positions ``pos``: the post-update state of the packet's flow after
        every earlier packet of that flow in ``stream`` (the raw rows in
        submission order), with the sketch counting every earlier packet."""
        key, _, ts, length = raw_fields(stream)
        h = key_hashes(key)
        fl = self.flow
        mults = odd_multipliers(0x51E7C4, 8)[: fl["cms_depth"]]
        cells = ((h[:, None] * mults[None, :])
                 >> np.uint64(64 - fl["cms_width_pow2"])).astype(np.int64)
        est = np.full(stream.shape[0], FLOW_CODE_MAX, np.int64)
        for d in range(cells.shape[1]):
            order = np.argsort(cells[:, d], kind="stable")
            c = cells[order, d]
            start = np.r_[True, c[1:] != c[:-1]]
            ar = np.arange(c.size)
            rank = np.empty(c.size, np.int64)
            rank[order] = ar - np.maximum.accumulate(np.where(start, ar, 0))
            est = np.minimum(est, np.minimum(rank + 1, FLOW_CODE_MAX))
        want = {int(p) for p in pos}
        # every packet of every flow that owns a wanted packet, in order
        members = np.nonzero(np.isin(h, np.unique(h[pos])))[0]
        out = {}
        state = {}
        for p in members.tolist():
            k = key[p].tobytes()
            feats, state[k] = self._flow_step(state.get(k), int(ts[p]),
                                              int(length[p]), int(est[p]))
            if p in want:
                out[p] = feats
        return np.asarray([out[int(p)] for p in pos], np.int32).reshape(
            len(pos), 8)

    def _flow_step(self, row, t: int, ln: int, est: int):
        fl, frac = self.flow, self.frac

        def shl(v, s=frac):
            return min(max(v, 0), FLOW_CODE_MAX >> s) << s

        def ewma(old, new):
            return old + int(rshift_round(np.int64(new - old),
                                          fl["ewma_shift"]))
        ln = max(ln, 0)
        len_q = shl(ln)
        if row is None:
            cnt, byte, first = 1, min(ln, FLOW_CODE_MAX), t
            iat_e, len_e, mn, mx = 0, len_q, ln, ln
        else:
            cnt0, byte0, last, first, iat0, len0, mn0, mx0 = row
            iat_q = shl(max(t - last, 0))
            iat_e = iat_q if cnt0 == 1 else ewma(iat0, iat_q)
            len_e = ewma(len0, len_q)
            mn, mx = min(mn0, ln), max(mx0, ln)
            byte = min(byte0 + ln, FLOW_CODE_MAX)
            cnt = min(cnt0 + 1, FLOW_CODE_MAX)
        feats = (shl(cnt), shl(byte >> fl["byte_shift"]), iat_e, len_e,
                 shl(mn), shl(mx), shl(max(t - first, 0) >> fl["dur_shift"]),
                 shl(est))
        return feats, (cnt, byte, t, first, iat_e, len_e, mn, mx)

    def gather(self, feats: np.ndarray, mid: np.ndarray) -> np.ndarray:
        """Each tenant's input columns from its FeatureSpec (unused
        columns read zero)."""
        x = np.zeros((mid.shape[0], self.width), np.int32)
        for i, m in enumerate(mid.tolist()):
            spec = self.tenants[m]["spec"]
            x[i, : len(spec)] = feats[i, list(spec)]
        return x

    # -- device lanes -------------------------------------------------------

    def _activate(self, y: np.ndarray, op: int) -> np.ndarray:
        frac = self.frac
        if op == 1:
            return np.maximum(y, 0)
        if op == 2:
            xc = np.clip(y, -(1 << 14), 1 << 14)
            s = np.full(y.shape, self.sig[-1], np.int32)
            for c in self.sig[-2::-1]:
                s = rshift_round(s * xc, frac) + np.int32(c)
            return s
        if op == 3:
            return np.where(y > 0, y, rshift_round(y * np.int32(self.alpha_q),
                                                   frac))
        if op == 4:
            return np.clip(np.int32(1 << (frac - 1)) + rshift_round(y, 2),
                           0, 1 << frac).astype(np.int32)
        return y

    def _mlp(self, x: np.ndarray, t: dict) -> np.ndarray:
        w_pad = np.zeros((self.width, self.width), np.int64)
        b_pad = np.zeros(self.width, np.int64)
        for w, b, op in zip(t["w"], t["b"], t["acts"]):
            w_pad[:] = 0
            b_pad[:] = 0
            w_pad[: w.shape[0], : w.shape[1]] = w
            b_pad[: b.shape[0]] = b
            acc = ((x.astype(np.int64) @ w_pad) + b_pad).astype(np.int32)
            x = self._activate(rshift_round(acc, self.frac), op)
        return x

    def _forest(self, x: np.ndarray, t: dict) -> np.ndarray:
        out = np.zeros((x.shape[0], self.width), np.int32)
        rows = np.arange(x.shape[0])
        for tree, on in zip(t["nodes"], t["tree_on"]):
            if not on:
                continue
            cur = np.zeros(x.shape[0], np.int64)
            for _ in range(self.depth):
                go_left = x[rows, tree[cur, 0]] <= tree[cur, 1]
                cur = np.where(go_left, tree[cur, 2], tree[cur, 3])
            leaf = tree[cur, 4]
            if t["mode"] == CLASSIFY:
                out[rows, leaf] += np.int32(1 << self.frac)
            else:
                out[:, 0] += leaf
        return out

    def outputs(self, x: np.ndarray, mid: np.ndarray) -> np.ndarray:
        out = np.zeros((x.shape[0], self.width), np.int32)
        lane = np.arange(self.width)[None, :]
        for m in np.unique(mid).tolist():
            sel = np.nonzero(mid == m)[0]
            t = self.tenants.get(m)
            if t is None:
                continue
            y = (self._mlp(x[sel], t) if t["kind"] == "mlp"
                 else self._forest(x[sel], t))
            out[sel] = np.where(lane < t["out_dim"], y, 0)
        return out

    def egress(self, x: np.ndarray, mid: np.ndarray,
               flags: np.ndarray) -> np.ndarray:
        """Egress rows: header with RESULT set, outputs as the payload."""
        return encode_wire(mid, self.frac, self.outputs(x, mid),
                           flags=flags | FLAG_RESULT, output_cnt=self.width,
                           feature_cnt=self.width)


def parse_wire(rows: np.ndarray, width: int):
    """``(model_id, flags, features)`` of encapsulated rows (features past
    the declared count read zero)."""
    rows = np.ascontiguousarray(rows, np.uint8)
    mid = (rows[:, 0].astype(np.int32) << 8) | rows[:, 1]
    cnt = rows[:, 2].astype(np.int32)
    flags = rows[:, 6].astype(np.int32)
    x = np.ascontiguousarray(rows[:, HEADER_BYTES: HEADER_BYTES + 4 * width]
                             ).view(">i4").astype(np.int32)
    x = np.where(np.arange(width)[None, :] < cnt[:, None], x, 0)
    return mid.astype(np.int32), flags, x
