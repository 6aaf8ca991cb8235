"""Collective utilities: HLO collective-bytes accounting (for the roofline)
and int8-compressed gradient all-reduce (paper C1 applied to the wire).

The roofline's collective term cannot come from ``cost_analysis()`` (XLA does
not report collective bytes), so :func:`collective_bytes` parses the compiled
HLO text and sums operand sizes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute op.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map

__all__ = ["collective_bytes", "compressed_all_reduce", "shard_map",
           "DTYPE_BYTES"]

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  "bf16[16,1024,512]{2,1,0} all-gather(...)"  possibly inside a tuple:
#       "(f32[128]{0}, f32[128]{0}) all-reduce(..."
_OP_RE = re.compile(
    r"=\s*(?P<outs>\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s*"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"(?P<dt>[a-z0-9]+)\[(?P<dims>[0-9,]*)\]")


def _shape_bytes(dt: str, dims: str) -> int:
    if dt not in DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * DTYPE_BYTES[dt]


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum output-shape bytes per collective kind from HLO text.

    Counts each op once (``-start`` variants counted, ``-done`` skipped via
    the regex's start/done alternation being tied to a single '=' def —
    '-done' ops re-list the same shape, so we drop them explicitly).
    """
    out: Dict[str, int] = defaultdict(int)
    counts: Dict[str, int] = defaultdict(int)
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue  # async completion: shape already counted at -start
        m = _OP_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        total = sum(_shape_bytes(s.group("dt"), s.group("dims"))
                    for s in _SHAPE_RE.finditer(m.group("outs")))
        out[op] += total
        counts[op] += 1
    result = dict(out)
    result["_counts"] = dict(counts)
    result["total"] = sum(v for k, v in out.items())
    return result


# ---------------------------------------------------------------------------
# int8-compressed all-reduce (beyond-paper C1: fixed-point on the wire)
# ---------------------------------------------------------------------------


def compressed_all_reduce(x: jax.Array, axis_name: str, bits: int = 8
                          ) -> jax.Array:
    """All-reduce with int8 fixed-point codes on the wire (~4× fewer bytes
    than an f32 ring all-reduce).

    Two-phase quantized reduction inside ``shard_map``:
      1. slice locally into N chunks, quantize (per-chunk absmax scale),
         ``all_to_all`` the int8 codes (+tiny f32 scales): each device
         receives every peer's copy of ITS chunk — 1 B/elem on the wire;
      2. dequantize-sum locally, re-quantize the reduced chunk, ``all_gather``
         codes back — ≈1 B/elem.
    Total ≈2 B/elem vs ≈8 B/elem for f32 ring all-reduce.
    """
    n = jax.lax.axis_size(axis_name)
    orig_shape = x.shape
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.shape[0]) % n
    flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(n, -1)  # chunk i → device i

    qmax = 2.0 ** (bits - 1) - 1
    scale = jnp.maximum(jnp.abs(chunks).max(axis=1, keepdims=True), 1e-12) / qmax
    codes = jnp.clip(jnp.round(chunks / scale), -qmax - 1, qmax).astype(jnp.int8)

    # phase 1: exchange codes so device i holds all peers' chunk-i
    codes_t = jax.lax.all_to_all(codes[:, None, :], axis_name, split_axis=0,
                                 concat_axis=1, tiled=False)  # (1, N, C)
    scales_t = jax.lax.all_to_all(scale[:, None, :], axis_name, 0, 1)
    reduced = (codes_t.astype(jnp.float32) * scales_t).sum(axis=(0, 1))  # (C,)

    # phase 2: re-quantize reduced chunk, gather all chunks
    r_scale = jnp.maximum(jnp.abs(reduced).max(), 1e-12) / qmax
    r_codes = jnp.clip(jnp.round(reduced / r_scale), -qmax - 1, qmax
                       ).astype(jnp.int8)
    all_codes = jax.lax.all_gather(r_codes, axis_name)  # (N, C)
    all_scales = jax.lax.all_gather(r_scale, axis_name)  # (N,)
    full = (all_codes.astype(jnp.float32) * all_scales[:, None]).reshape(-1)
    if pad:
        full = full[:-pad]
    return full.reshape(orig_shape)
