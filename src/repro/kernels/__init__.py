"""Pallas TPU kernels for the paper's compute hot-spots.

  * ``fixedpoint_matmul``  — W8A8 int8→int32 MXU GEMM + Table-2 rescale (C1)
  * ``taylor_activation``  — fused integer-Horner polynomial activation (C2)
  * ``fixedpoint_mlp``     — fused multi-model MLP: the whole batched
                             data-plane layer loop (masked Model-ID GEMM,
                             bias, requantize, opcode-selected activation)
                             in one kernel over the stacked tables.  Two
                             weight-lane variants (``KERNEL_VARIANTS``):
                             ``"int16"`` (exact bf16 digit-plane dots) and
                             ``"int8"`` (saturating int8 lane,
                             int8×int8→int32 dot — v5e MXU native rate),
                             both bit-exact against their jnp oracles
  * ``forest_traverse``    — (module ``forest_traversal``) fused
                             multi-forest tree-ensemble traversal, two
                             lowerings of one oracle (``FOREST_VARIANTS``):
                             ``"chase"`` — per-packet forest select +
                             level-bounded node pointer chase unrolled to
                             ``max_depth`` + majority/mean vote; ``"range"``
                             — the pForest range-table form (parallel
                             threshold compares + leaf-mask AND-reduce,
                             exit leaf = lowest set bit), both in one
                             kernel over the stacked forest tables
  * ``fused_serve``        — ``serve_lanes``, the lane-dispatch core of the
                             one-dispatch serving program both engine
                             surfaces share
  * ``flow_update``        — (module ``flow_update``) stateful per-flow
                             register update + feature emit for the flow
                             engine (``repro.flow``): sequential scatter
                             over the register file + count-min sketch, a
                             rank-round vectorized host lowering on every
                             platform, bit-exact vs the pure-Python oracle
                             ``ref.flow_update_numpy``
  * ``wkv_scan``           — chunked RWKV-6 WKV scan with the recurrent
                             state resident in VMEM across chunks (the
                             §Perf rwkv hillclimb's end-state)

Each kernel ships with a pure-jnp oracle (`ref.py`; the forest additionally
has a pure-Python scalar oracle); `ops.py` wrappers pick one lowering per
platform (TPU: native Pallas; CPU: the gathered jnp lowering).
"""

from . import ops, ref, wkv_scan
from .ops import (FOREST_VARIANTS, KERNEL_VARIANTS, fixedpoint_matmul,
                  flow_update, forest_traverse, fused_mlp, taylor_activation)
from .wkv_scan import wkv_scan_pallas

__all__ = ["ops", "ref", "wkv_scan", "fixedpoint_matmul",
           "taylor_activation", "fused_mlp", "forest_traverse",
           "flow_update", "wkv_scan_pallas", "KERNEL_VARIANTS",
           "FOREST_VARIANTS"]
