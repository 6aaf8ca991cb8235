"""Needed work and the chip's peaks.

Work is counted from the installed tenants, never from a kernel's shapes,
so it reads the same whatever a later lowering computes:

* MLP: ``2 * sum_l d_in * d_out`` integer operations per packet of the
  packet's own tenant (padding rows and other tenants' slots count zero);
* forest: one compare per level walked per tree (the reference walks
  ``max_tree_depth`` levels);
* bytes: every real row's features in and outputs out, plus the lane's
  installed tables read once per device batch.

Shares divide by the int8 peak, because the arithmetic is integer; that
divisor stays whatever lowering later PRs choose.
"""

from __future__ import annotations

# Published peaks of one chip, keyed by ``device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB HBM at 819 GB/s).
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/benchlib/work.py")
    return PEAKS[device_kind]


def tenant_ops(t: dict, max_depth: int) -> int:
    """Integer operations one packet of tenant ``t`` needs."""
    if t["kind"] == "mlp":
        return sum(2 * w.shape[0] * w.shape[1] for w in t["w"])
    return int(t["tree_on"].sum()) * max_depth


def table_bytes(tenants: list, kind: str, weight_bits: int) -> int:
    """Bytes of the installed tables of one lane."""
    n = 0
    for t in tenants:
        if t["kind"] != kind:
            continue
        if kind == "mlp":
            n += sum(w.size * weight_bits // 8 + b.size * 4
                     for w, b in zip(t["w"], t["b"]))
        else:
            n += int(t["nodes"].size) * 4
    return n


def row_bytes(width: int) -> int:
    """Bytes one real row moves: int32 features in, int32 outputs out."""
    return 2 * 4 * width


def roofline_share(ops: float, nbytes: float, seconds: float,
                   pk: dict) -> float:
    """Least time the work could take on the chip over the time it took,
    in percent."""
    least = max(ops / pk["int8_ops"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / seconds


# HLO instruction names of each lane's Pallas kernel in the v5e trace
# (``XLA Ops`` line, see ``tracefile.op_name``): the kernel functions'
# own names, which the trace shows today.
KERNEL_OPS = {"mlp": ("fixedpoint_mlp_pallas",),
              "forest": ("forest_range_pallas", "forest_traverse_pallas")}


def kernel_events(trace: dict, lane: str):
    """``(seconds, events)`` of one lane's kernel in a reduced trace."""
    pats = KERNEL_OPS[lane]
    sec = n = 0
    for name, s in trace["op_seconds"].items():
        if name in pats:
            sec += s
            n += trace["op_counts"][name]
    return sec, n


def kernel_roofline(ctx, lane: str):
    """A lane kernel's roofline share, or ``None`` where the trace holds
    none of its events or the window computed none of its rows."""
    if ctx.trace is None or ctx.peaks is None:
        return None
    sec, n = kernel_events(ctx.trace, lane)
    w = ctx.work.get(lane)
    if not sec or not n or not w or not w["rows"]:
        return None
    nbytes = w["row_bytes"] + w["table_bytes"] * n
    return roofline_share(w["ops"], nbytes, sec, ctx.peaks)
