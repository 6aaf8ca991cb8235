"""Byte layouts and hashes that the deployment's semantics fix.

* the raw header a P4 parser extracts before any feature exists: the
  13-byte 5-tuple key, then model id (2), arrival tick (4) and wire length
  (2), all big-endian;
* the encapsulation header of paper Table 1 (Model ID 16 · Feature Cnt 8 ·
  Output Cnt 8 · Scale 16 · Flags 8 bits) followed by big-endian int32
  feature codes;
* the 64-bit flow-key hash (word-wise multiply-add plus the splitmix64
  finaliser) that picks the count-min sketch cells.

The traffic generators and the plain reference both use these.  They are
written here from the layout, so the benchmark imports nothing of the
program to build its inputs or its expected answers.
"""

from __future__ import annotations

import numpy as np

RAW_KEY_BYTES = 13
RAW_HEADER_BYTES = RAW_KEY_BYTES + 8
HEADER_BYTES = 7
FLAG_RESULT = 0x02

_MIX1 = np.uint64(0xBF58476D1CE4E5B9)
_MIX2 = np.uint64(0x94D049BB133111EB)


def odd_multipliers(seed: int, n: int) -> np.ndarray:
    """``n`` odd 64-bit multipliers drawn from ``seed`` (the hash family of
    the flow key and of the count-min sketch rows)."""
    r = np.random.default_rng(seed).integers(0, 2 ** 63, n, np.uint64)
    return (r << np.uint64(1)) | np.uint64(1)


_KEY_MULTS = odd_multipliers(0xC0FFEE, 2)


_RAW_RECORD = np.dtype([("src_ip", ">u4"), ("dst_ip", ">u4"),
                        ("src_port", ">u2"), ("dst_port", ">u2"),
                        ("proto", "u1"), ("model_id", ">u2"), ("ts", ">u4"),
                        ("length", ">u2")])


def encode_raw_headers(src_ip, dst_ip, src_port, dst_port, proto, model_id,
                       ts, length) -> np.ndarray:
    """Raw header rows ``(B, RAW_HEADER_BYTES)`` uint8, big-endian fields
    (each value taken modulo its field width)."""
    b = np.asarray(src_ip).shape[0]
    rec = np.empty(b, _RAW_RECORD)
    for name, val in (("src_ip", src_ip), ("dst_ip", dst_ip),
                      ("src_port", src_port), ("dst_port", dst_port),
                      ("proto", proto), ("model_id", model_id), ("ts", ts),
                      ("length", length)):
        bits = 8 * rec.dtype[name].itemsize
        rec[name] = np.asarray(val, np.int64) & ((1 << bits) - 1)
    return rec.view(np.uint8).reshape(b, RAW_HEADER_BYTES)


def raw_fields(raw: np.ndarray):
    """``(key_bytes, model_id, ts, length)`` of raw header rows."""
    raw = np.ascontiguousarray(raw, np.uint8)

    def be(col, nbytes):
        v = np.zeros(raw.shape[0], np.int64)
        for i in range(nbytes):
            v = (v << 8) | raw[:, col + i]
        return v
    return (raw[:, :RAW_KEY_BYTES], be(13, 2).astype(np.int32),
            be(15, 4).astype(np.int32), be(19, 2).astype(np.int32))


def key_hashes(key_bytes: np.ndarray) -> np.ndarray:
    """64-bit hash of each 5-tuple key (uint64 arithmetic wraps)."""
    n = key_bytes.shape[0]
    buf = np.zeros((n, 16), np.uint8)
    buf[:, :RAW_KEY_BYTES] = key_bytes
    words = buf.view(np.uint64)
    h = words[:, 0] * _KEY_MULTS[0] + words[:, 1] * _KEY_MULTS[1]
    h ^= h >> np.uint64(30)
    h *= _MIX1
    h ^= h >> np.uint64(27)
    h *= _MIX2
    h ^= h >> np.uint64(31)
    return h


def encode_wire(model_id, scale, features_q: np.ndarray, *, flags=0,
                output_cnt=0, feature_cnt=None) -> np.ndarray:
    """Encapsulated rows ``(B, HEADER_BYTES + 4 F)`` uint8 (Table 1)."""
    features_q = np.asarray(features_q, np.int32)
    b, f = features_q.shape
    out = np.empty((b, HEADER_BYTES + 4 * f), np.uint8)

    def field(col, val, nbytes):
        val = np.broadcast_to(np.asarray(val, np.int64), (b,))
        for i in range(nbytes):
            out[:, col + i] = (val >> (8 * (nbytes - 1 - i))) & 0xFF
    field(0, model_id, 2)
    field(2, f if feature_cnt is None else feature_cnt, 1)
    field(3, output_cnt, 1)
    field(4, scale, 2)
    field(6, flags, 1)
    out[:, HEADER_BYTES:] = np.ascontiguousarray(
        features_q.astype(">i4")).view(np.uint8).reshape(b, 4 * f)
    return out
