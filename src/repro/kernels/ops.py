"""jit'd public wrappers around the Pallas kernels with platform dispatch.

Each lane has one lowering per platform, chosen by ``backend="auto"``: the
Pallas kernel on TPU, the gathered jnp lowering in ``ref.py`` on CPU (which
XLA:CPU vectorizes).  Nothing falls back at run time: a kernel that does not
lower raises.  Two explicit choices exist for cross-checks:

  * ``"pallas"``    — force the kernel (interpret=True off-TPU)
  * ``"ref"``       — force the masked jnp oracle

Wrappers own the padding to block multiples so callers see arbitrary shapes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .fixedpoint_matmul import BK, BM, BN, fixedpoint_matmul_pallas
from .fixedpoint_mlp import BB, KERNEL_VARIANTS, fixedpoint_mlp_pallas
from .flow_update import flow_update_gather
from .forest_traversal import (FB, FOREST_VARIANTS, forest_range_pallas,
                               forest_traverse_pallas)
from .taylor_activation import BC, BR, taylor_activation_pallas

__all__ = ["fixedpoint_matmul", "taylor_activation", "fused_mlp",
           "forest_traverse", "flow_update", "on_tpu", "KERNEL_VARIANTS",
           "FOREST_VARIANTS"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x: jax.Array, mults: tuple) -> jax.Array:
    pads = [(0, (-d) % m) for d, m in zip(x.shape, mults)]
    if any(p[1] for p in pads):
        return jnp.pad(x, pads)
    return x


def fixedpoint_matmul(x_codes: jax.Array, w_codes: jax.Array,
                      x_scale: jax.Array, w_scale: jax.Array,
                      backend: str = "auto") -> jax.Array:
    """W8A8 GEMM: (M,K) int8 · (K,N) int8 with per-row/col scales → f32."""
    m, k = x_codes.shape
    _, n = w_codes.shape
    use_pallas = backend == "pallas" or (backend == "auto" and on_tpu())
    if not use_pallas:
        return ref.fixedpoint_matmul_ref(x_codes, w_codes, x_scale, w_scale)
    xp = _pad_to(x_codes, (BM, BK))
    wp = _pad_to(w_codes, (BK, BN))
    xs = _pad_to(x_scale, (BM, 1))
    ws = _pad_to(w_scale, (1, BN))
    out = fixedpoint_matmul_pallas(xp, wp, xs, ws, interpret=not on_tpu())
    return out[:m, :n]


def fused_mlp(x_q: jax.Array, slot: jax.Array, w: jax.Array, b: jax.Array,
              act: jax.Array, layer_on: jax.Array, *, frac: int,
              sig_coeffs, leaky_alpha_q: int,
              backend: str = "auto", variant: str = "int16") -> jax.Array:
    """Fused multi-model fixed-point MLP over *stacked* control-plane tables.

    Layout prep lives here so callers hand over tables exactly as the
    control plane stores them:

      x_q (B, W) int32 · slot (B,) int32 · w (M, L, W, W) · b (M, L, W) ·
      act (M, L) · layer_on (M, L)  →  (B, W) int32 output codes.

    The kernel wants layer-major stacked operands — w as ``(L, M·W, W)`` so
    the per-packet model select becomes one GEMM over the fused (model,
    feature) axis — and a batch padded to the tile size.  Padded rows run
    slot 0 and are sliced off (outputs for real rows are unaffected: the
    masked GEMM is row-independent).

    ``variant`` selects the weight lane (``kernels.KERNEL_VARIANTS``):
    ``"int16"`` takes weights of up to 16 bits (bf16 digit-plane dots in the
    kernel); ``"int8"`` saturates feature
    codes into the int8 lane per layer and narrows both dot operands to int8
    (v5e MXU native rate).  Weight codes must already fit int8 — install
    models through a ``ControlPlane(weight_bits=8)``; the engine rejects an
    int8-variant configuration over a wider weight format rather than let
    the lane cast silently truncate a model the caller believes is 16-bit.
    """
    if backend not in ("auto", "pallas", "ref"):
        raise ValueError(f"unknown backend: {backend!r}")
    if variant not in KERNEL_VARIANTS:
        raise ValueError(f"unknown kernel variant: {variant!r}")
    n_batch, width = x_q.shape
    n_models, n_layers = act.shape
    use_pallas = backend == "pallas" or (backend == "auto" and on_tpu())
    coeffs = tuple(int(c) for c in np.asarray(sig_coeffs).tolist())
    lane_bits = 8 if variant == "int8" else None
    if backend == "auto" and not on_tpu():
        # CPU lowering: XLA:CPU scalarizes wide s32 GEMMs, so the masked-GEMM
        # form is slow there — the bit-identical gathered batched-matvec
        # (elementwise multiply + reduce, fully vectorized in int32) wins.
        # Still one XLA program for the whole layer loop.
        return ref.fused_mlp_gather_ref(
            x_q, slot.astype(jnp.int32), w, b, act, layer_on, frac=frac,
            sig_coeffs=coeffs, leaky_alpha_q=leaky_alpha_q,
            lane_bits=lane_bits)
    # Layer-major stacked operands for the kernel/oracle (masked-GEMM form).
    # These transposes are retraced per batch; they scale with M·L·W² (table
    # size, ~KBs at paper scale), not batch size.  Hoisting them into the
    # per-generation ControlPlane snapshot is the known TPU optimization
    # (ROADMAP: multi-backend fused kernel) — needs a layer-major ModelTables
    # variant and a device to measure on.
    # (the weight table keeps its storage dtype: the "int16" kernel sizes
    # its digit split by it)
    wl = jnp.transpose(w, (1, 0, 2, 3)).reshape(
        n_layers, n_models * width, width)
    bl = jnp.transpose(b, (1, 0, 2)).astype(jnp.int32)
    al = jnp.transpose(act, (1, 0)).astype(jnp.int32)[:, :, None]
    onl = jnp.transpose(layer_on, (1, 0)).astype(jnp.int32)[:, :, None]
    slot2 = slot.astype(jnp.int32)[:, None]
    if not use_pallas:  # backend == "ref": the masked-GEMM oracle
        return ref.fused_mlp_ref(x_q, slot2, wl.astype(jnp.int32), bl, al,
                                 onl, frac=frac, sig_coeffs=coeffs,
                                 leaky_alpha_q=leaky_alpha_q,
                                 lane_bits=lane_bits)
    if variant == "int8":
        # the int8 lane feeds the MXU int8 weight codes directly; the cast
        # is exact because the control plane's weight_bits=8 format already
        # saturated the codes into the lane
        wl = wl.astype(jnp.int8)
    xp = _pad_to(x_q, (BB, 1))
    sp = _pad_to(slot2, (BB, 1))
    out = fixedpoint_mlp_pallas(xp, sp, wl, bl, al, onl, frac=frac,
                                sig_coeffs=coeffs,
                                leaky_alpha_q=leaky_alpha_q,
                                variant=variant,
                                interpret=not on_tpu())
    return out[:n_batch]


def forest_traverse(x_q: jax.Array, slot: jax.Array, nodes: jax.Array,
                    tree_on: jax.Array, mode: jax.Array, *, max_depth: int,
                    frac: int, backend: str = "auto",
                    variant: str = "chase",
                    ranges=None) -> jax.Array:
    """Fused multi-forest traversal over *stacked* control-plane node tables.

    Layout prep lives here so callers hand over tables exactly as the
    control plane stores them:

      x_q (B, W) int32 · slot (B,) int32 · nodes (F, T, N, 5) int32 ·
      tree_on (F, T) int32 · mode (F,) int32  →  (B, W) int32 output codes
      (``ref.FOREST_REGRESS``: lane 0 = Σ leaf codes; ``FOREST_CLASSIFY``:
      lane c = ``1 << frac`` per tree voting class c).

    The kernel wants tree-major field-major operands — ``nodes_t`` as
    ``(T, F, 5·N)`` so the per-packet forest select is one row select per
    tree — and a batch padded to the tile size.  Padded rows run slot 0 and are
    sliced off (the masked traversal is row-independent).  Backend dispatch
    mirrors ``fused_mlp``: Pallas on TPU (interpreted when forced off-TPU),
    the gathered batched lowering on CPU, the masked jnp oracle for
    ``backend="ref"``.

    ``variant`` selects the traversal lowering (``FOREST_VARIANTS``):
    ``"chase"`` is the level-bounded pointer chase over ``nodes``;
    ``"range"`` is the pForest range-table form (parallel compares +
    leaf-mask AND-reduce) over ``ranges`` — a ``(feat, thresh, lmask,
    payload)`` tuple or a ``control_plane.RangeTables`` (the dense
    ``nodes`` argument is then only read for its shape).  Both variants are
    bit-exact against the same scalar oracle ``ref.forest_traverse_numpy``;
    the chase does less total work (visited nodes only) and stays the
    measured CPU default, the range form has no serial step dependency —
    the vector-unit trade (see forest_traversal.FOREST_VARIANTS).
    """
    if backend not in ("auto", "pallas", "ref"):
        raise ValueError(f"unknown backend: {backend!r}")
    if variant not in FOREST_VARIANTS:
        raise ValueError(f"unknown forest variant: {variant!r}")
    n_batch, _ = x_q.shape
    n_forests, n_trees, n_nodes, _ = nodes.shape
    use_pallas = backend == "pallas" or (backend == "auto" and on_tpu())
    if variant == "range":
        if ranges is None:
            raise ValueError("variant='range' needs the compiled range "
                             "tables (ControlPlane.range_tables())")
        feat, thresh, lmask, payload = (
            (ranges.feat, ranges.thresh, ranges.lmask, ranges.payload)
            if hasattr(ranges, "lmask") else ranges)
        if backend == "auto" and not on_tpu():
            return ref.forest_range_gather_ref(
                x_q, slot.astype(jnp.int32), feat, thresh, lmask, payload,
                tree_on, mode, frac=frac)
        ni = feat.shape[-1]
        nl = payload.shape[-1]
        # tree-major field-major columns: feat | thresh | mask | payload
        mask_i32 = jax.lax.bitcast_convert_type(lmask, jnp.int32)
        rng_t = jnp.concatenate(
            [jnp.transpose(jnp.asarray(a, jnp.int32), (1, 0, 2))
             for a in (feat, thresh, mask_i32, payload)], axis=2)
        on_t = jnp.transpose(tree_on, (1, 0)).astype(jnp.int32)[:, :, None]
        mode2 = mode.astype(jnp.int32)[:, None]
        slot2 = slot.astype(jnp.int32)[:, None]
        if not use_pallas:  # backend == "ref": the masked jnp oracle
            return ref.forest_range_ref(x_q, slot2, rng_t, on_t, mode2,
                                        n_entries=ni, n_leaves=nl, frac=frac)
        xp = _pad_to(x_q, (FB, 1))
        sp = _pad_to(slot2, (FB, 1))
        out = forest_range_pallas(xp, sp, rng_t, on_t, mode2, n_entries=ni,
                                  n_leaves=nl, frac=frac,
                                  interpret=not on_tpu())
        return out[:n_batch]
    if backend == "auto" and not on_tpu():
        # CPU lowering: the per-packet table gather + vectorized pointer
        # chase (take_along_axis) vectorizes on XLA:CPU; the masked form's
        # wide one-hot s32 dots scalarize there, like the MLP's.
        return ref.forest_traverse_gather_ref(
            x_q, slot.astype(jnp.int32), nodes, tree_on, mode,
            max_depth=max_depth, frac=frac)
    # Tree-major stacked operands with field-major columns:
    # nodes_t[t, f, field*N + n] == nodes[f, t, n, field].
    nodes_t = jnp.transpose(nodes, (1, 0, 3, 2)).astype(jnp.int32).reshape(
        n_trees, n_forests, 5 * n_nodes)
    on_t = jnp.transpose(tree_on, (1, 0)).astype(jnp.int32)[:, :, None]
    mode2 = mode.astype(jnp.int32)[:, None]
    slot2 = slot.astype(jnp.int32)[:, None]
    if not use_pallas:  # backend == "ref": the literal kernel oracle
        return ref.forest_traverse_ref(x_q, slot2, nodes_t, on_t, mode2,
                                       max_depth=max_depth, frac=frac)
    xp = _pad_to(x_q, (FB, 1))
    sp = _pad_to(slot2, (FB, 1))
    out = forest_traverse_pallas(xp, sp, nodes_t, on_t, mode2,
                                 max_depth=max_depth, frac=frac,
                                 interpret=not on_tpu())
    return out[:n_batch]


def flow_update(state, cms, slots, cells, ts, length, live, *, frac: int,
                ewma_shift: int = 3, byte_shift: int = 6,
                dur_shift: int = 10, backend: str = "auto",
                copy: bool = True, rank=None):
    """Stateful per-flow register update + feature emit for one fixed-shape
    batch of parsed raw headers (see ``kernels.flow_update`` for the stage's
    role and ``ref.flow_update_numpy`` for the exact semantics).

    Returns ``(new_state, new_cms, features)``.  Unlike the stateless
    kernels this op carries *state through time*: the caller (the flow
    engine) owns the register file and feeds each batch the previous
    batch's output state.

    The stage runs on the host on every platform: ``"auto"`` is the numpy
    rank-round lowering, because the register file lives next to the flow
    hash table (which owns eviction and failover migration) and the
    rank-round walk beats any sequential device scan.  ``copy=False`` lets
    it update the register file in place — the serving hot path.  ``rank``
    optionally carries each packet's within-flow occurrence order (the flow
    table computes it as a dedup by-product) so the lowering skips
    re-ranking.  ``"ref"`` runs the pure-Python oracle and returns fresh
    arrays.
    """
    kw = dict(frac=frac, ewma_shift=ewma_shift, byte_shift=byte_shift,
              dur_shift=dur_shift)
    if backend == "ref":
        return ref.flow_update_numpy(state, cms, slots, cells, ts, length,
                                     live, **kw)
    if backend != "auto":
        raise ValueError(f"unknown backend: {backend!r}")
    return flow_update_gather(np.asarray(state), np.asarray(cms), slots,
                              cells, ts, length, live, copy=copy, rank=rank,
                              **kw)


def taylor_activation(x_q: jax.Array, coeffs, x_frac: int,
                      backend: str = "auto") -> jax.Array:
    """Integer-Horner polynomial activation on int32 codes (any shape)."""
    coeffs = tuple(int(c) for c in np.asarray(coeffs).tolist())
    use_pallas = backend == "pallas" or (backend == "auto" and on_tpu())
    if not use_pallas:
        clamp = (1 << 14) - 1
        return ref.taylor_activation_ref(
            jnp.clip(x_q, -clamp, clamp), np.asarray(coeffs), x_frac)
    shape = x_q.shape
    flat = x_q.reshape(-1)
    total = flat.shape[0]
    # pad to a whole number of (BR, BC) tiles and reshape to 2-D
    padded = _pad_to(flat.reshape(1, total), (1, BR * BC))
    x2 = padded.reshape(-1, BC)
    out = taylor_activation_pallas(x2, coeffs, x_frac, interpret=not on_tpu())
    return out.reshape(-1)[:total].reshape(shape)
