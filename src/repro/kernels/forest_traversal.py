"""Pallas TPU kernel: fused multi-forest tree-ensemble traversal (the whole
tree-inference stage of the data plane in one kernel).

The forest control plane (``ControlPlane.install_forest``) packs every
installed random forest into dense padded node tables — the pForest/Planter
match-action analogue: one table row per tree node holding (feature index,
quantized threshold, left child, right child, leaf payload).  A mixed packet
batch carries per-packet Model IDs resolved to forest slots, so — exactly
like the fused MLP kernel — the traversal must use each packet's own tables
without gathering per-packet node tensors from HBM.

Formulation (per batch tile, all tables resident in VMEM):

  1. forest select, once per tree: ``tbl[p] = nodes[t, slot[p]]`` — an
     F-row select chain (``ref.slot_select``, VPU; the matrix unit has no
     exact int32 dot) that hands every packet its own tree's node table,
     field-major (feat | thresh | left | right | leaf columns);
  2. level-bounded pointer chase, unrolled to ``max_depth``: the current
     node's fields are iota-compare row reductions over the gathered table
     (VPU), the split feature value is the same reduction over the packet's
     feature lanes, and the child select is one ``where``.  Leaves self-loop
     (left == right == self), so after ``max_depth`` steps every lane holds a
     leaf with no per-step leaf test — the P4 analogue is a fixed pipeline of
     ``max_depth`` match-action stages;
  3. vote accumulate: classify forests one-hot their leaf's class lane with
     ``1 << frac`` per tree (majority = argmax at the consumer); regress
     forests sum pre-divided leaf codes into lane 0 (mean vote, the division
     folded into compile-time quantization).  Dead (padded) trees are masked
     by ``tree_on``.

Integer discipline matches the rest of the data plane: every comparison and
accumulation is int32, thresholds/leaves are fixed-point codes on the same
``frac`` grid as the wire features, so the kernel is bit-exact against the
pure-Python oracle ``ref.forest_traverse_numpy`` (asserted on every backend
by the tier-1 suite).  Off-TPU the kernel runs under the Pallas interpreter;
the fast CPU path is the gathered lowering ``ref.forest_traverse_gather_ref``
(selected by ``ops.forest_traverse``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import FOREST_CLASSIFY, slot_select

__all__ = ["forest_traverse_pallas", "forest_range_pallas", "FB",
           "FOREST_VARIANTS"]

# Traversal variants of the forest lane:
#   * "chase" — the PR-3 level-bounded pointer chase (kernel below): per
#     step, the current node's fields are masked row reductions and the
#     child select is one ``where`` — work scales with *visited* nodes
#     (depth per tree) but the steps are serially dependent.
#   * "range" — the pForest range-table lowering (``repro.forest.ranges``):
#     every range entry's ``x[feat] <= thresh`` comparison evaluates at
#     once, surviving-leaf masks of failed comparisons AND-reduce, and the
#     exit leaf is the lowest set bit — work scales with *all* internal
#     nodes, but there is no sequential dependency chain, which is the
#     right trade on a wide vector unit (the chase stays the measured CPU
#     default; see ops.forest_traverse).
FOREST_VARIANTS = ("chase", "range")

# Batch-tile rows per grid step.  The traversal working set per tile is the
# gathered tree table (bb, 5·N) plus a handful of (bb, 1) lanes — VMEM-tiny
# at paper scale (N ≤ a few hundred nodes).
FB = 128


def _kernel(x_ref, slot_ref, nodes_ref, on_ref, mode_ref, o_ref, *,
            max_depth: int, n_trees: int, n_nodes: int, frac: int):
    x = x_ref[...]        # (bb, W) int32 feature codes
    slot = slot_ref[...]  # (bb, 1) int32, pre-clamped to [0, F)
    bb, width = x.shape

    mode_p = slot_select(slot, mode_ref[...])  # (bb, 1)
    n_iota = jax.lax.broadcasted_iota(jnp.int32, (bb, n_nodes), 1)
    w_iota = jax.lax.broadcasted_iota(jnp.int32, (bb, width), 1)
    one_q = jnp.int32(1 << frac)

    def tree(t, acc):  # one tree per step: the unrolled body stays small
        # forest dispatch: every packet receives its own forest's node
        # table for tree t, field-major columns
        tbl = slot_select(slot, nodes_ref[t])
        feat_t = tbl[:, 0 * n_nodes: 1 * n_nodes]
        th_t = tbl[:, 1 * n_nodes: 2 * n_nodes]
        left_t = tbl[:, 2 * n_nodes: 3 * n_nodes]
        right_t = tbl[:, 3 * n_nodes: 4 * n_nodes]
        leaf_t = tbl[:, 4 * n_nodes: 5 * n_nodes]
        on = slot_select(slot, on_ref[t]) > 0
        cur = jnp.zeros((bb, 1), jnp.int32)
        for _ in range(max_depth):  # static: the P4 stage-count bound
            sel = (n_iota == cur).astype(jnp.int32)  # (bb, N)
            feat = jnp.sum(sel * feat_t, axis=1, keepdims=True)
            th = jnp.sum(sel * th_t, axis=1, keepdims=True)
            lf = jnp.sum(sel * left_t, axis=1, keepdims=True)
            rt = jnp.sum(sel * right_t, axis=1, keepdims=True)
            xv = jnp.sum(jnp.where(w_iota == feat, x, 0), axis=1,
                         keepdims=True)
            cur = jnp.where(xv <= th, lf, rt)  # leaves self-loop
        sel = (n_iota == cur).astype(jnp.int32)
        leaf = jnp.sum(sel * leaf_t, axis=1, keepdims=True)  # (bb, 1)
        vote_cls = jnp.where(w_iota == leaf, one_q, 0)
        vote_reg = jnp.where(w_iota == 0, leaf, 0)
        contrib = jnp.where(mode_p == FOREST_CLASSIFY, vote_cls, vote_reg)
        return acc + jnp.where(on, contrib, 0)

    o_ref[...] = jax.lax.fori_loop(0, n_trees, tree,
                                   jnp.zeros((bb, width), jnp.int32))


@functools.partial(jax.jit, static_argnames=("max_depth", "frac", "bb",
                                             "interpret"))
def forest_traverse_pallas(x_q: jax.Array, slot: jax.Array,
                           nodes_t: jax.Array, tree_on_t: jax.Array,
                           mode: jax.Array, *, max_depth: int, frac: int,
                           bb: int = FB, interpret: bool = False) -> jax.Array:
    """Fused multi-forest traversal on integer codes.

    x_q        (B, W)        int32 feature codes at ``frac`` fractional bits
    slot       (B, 1)        int32 forest slot per packet, in ``[0, F)``
    nodes_t    (T, F, 5·N)   int32 node tables, tree-major, field-major
                             columns (``ops.forest_traverse`` preps this from
                             the control plane's (F, T, N, 5) layout)
    tree_on_t  (T, F, 1)     int32 tree-exists flags
    mode       (F, 1)        int32 vote mode (ref.FOREST_REGRESS/CLASSIFY)
    Returns    (B, W)        int32 output codes (lane 0 sum / per-class votes)

    ``B % bb == 0`` (the ops.py wrapper pads).  ``max_depth`` is the static
    unroll bound — every packed tree's depth must not exceed it (the control
    plane validates at install).
    """
    n_batch, width = x_q.shape
    n_trees, n_forests, ncols = nodes_t.shape
    n_nodes = ncols // 5
    if n_batch % bb:
        # a floor-divided grid would silently leave the tail rows unwritten
        raise ValueError(f"batch {n_batch} not a multiple of tile {bb}; "
                         "use ops.forest_traverse, which pads")
    grid = (n_batch // bb,)
    return pl.pallas_call(
        functools.partial(_kernel, max_depth=max_depth, n_trees=n_trees,
                          n_nodes=n_nodes, frac=frac),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, width), lambda i: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i: (i, 0)),
            pl.BlockSpec((n_trees, n_forests, ncols), lambda i: (0, 0, 0)),
            pl.BlockSpec((n_trees, n_forests, 1), lambda i: (0, 0, 0)),
            pl.BlockSpec((n_forests, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bb, width), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_batch, width), jnp.int32),
        interpret=interpret,
    )(x_q, slot, nodes_t, tree_on_t, mode)


def _range_kernel(x_ref, slot_ref, rng_ref, on_ref, mode_ref, o_ref, *,
                  n_trees: int, n_entries: int, n_leaves: int, frac: int):
    """Range-table traversal: per tree, one slot select hands every packet
    its own forest's range rows (feat | thresh | mask | payload, field-major
    columns), then the whole tree evaluates as ``n_entries`` parallel
    compares + a leaf-mask AND-reduce — no pointer chase, no per-step
    serial dependency (the P4 analogue is a ternary-match range table)."""
    x = x_ref[...]        # (bb, W) int32 feature codes
    slot = slot_ref[...]  # (bb, 1) int32, pre-clamped to [0, F)
    bb, width = x.shape

    mode_p = slot_select(slot, mode_ref[...])  # (bb, 1)
    w_iota = jax.lax.broadcasted_iota(jnp.int32, (bb, width), 1)
    one_q = jnp.int32(1 << frac)
    all_ones = jnp.uint32(0xFFFFFFFF)

    def tree(t, acc):  # one tree per step: the unrolled body stays small
        tbl = slot_select(slot, rng_ref[t])
        feat_t = tbl[:, 0 * n_entries: 1 * n_entries]
        th_t = tbl[:, 1 * n_entries: 2 * n_entries]
        mask_t = tbl[:, 2 * n_entries: 3 * n_entries].astype(jnp.uint32)
        pay_t = tbl[:, 3 * n_entries: 3 * n_entries + n_leaves]
        on = slot_select(slot, on_ref[t]) > 0
        word = jnp.full((bb, 1), 0xFFFFFFFF, jnp.uint32)
        for i in range(n_entries):  # static: all entries, no serial chain
            fe = feat_t[:, i: i + 1]
            xv = jnp.sum(jnp.where(w_iota == fe, x, 0), axis=1,
                         keepdims=True)
            cond = xv <= th_t[:, i: i + 1]
            word = word & jnp.where(cond, all_ones, mask_t[:, i: i + 1])
        iso = word & (~word + jnp.uint32(1))       # lowest set bit
        below = iso - jnp.uint32(1)                # ones strictly below it
        l_iota = jax.lax.broadcasted_iota(jnp.uint32, (bb, n_leaves), 1)
        bits = ((below >> l_iota) & jnp.uint32(1)).astype(jnp.int32)
        leaf_idx = jnp.sum(bits, axis=1, keepdims=True)  # popcount(below)
        li32 = jax.lax.broadcasted_iota(jnp.int32, (bb, n_leaves), 1)
        leaf = jnp.sum(jnp.where(li32 == leaf_idx, pay_t, 0), axis=1,
                       keepdims=True)              # (bb, 1)
        vote_cls = jnp.where(w_iota == leaf, one_q, 0)
        vote_reg = jnp.where(w_iota == 0, leaf, 0)
        contrib = jnp.where(mode_p == FOREST_CLASSIFY, vote_cls, vote_reg)
        return acc + jnp.where(on, contrib, 0)

    o_ref[...] = jax.lax.fori_loop(0, n_trees, tree,
                                   jnp.zeros((bb, width), jnp.int32))


@functools.partial(jax.jit, static_argnames=("n_entries", "n_leaves", "frac",
                                             "bb", "interpret"))
def forest_range_pallas(x_q: jax.Array, slot: jax.Array, rng_t: jax.Array,
                        tree_on_t: jax.Array, mode: jax.Array, *,
                        n_entries: int, n_leaves: int, frac: int,
                        bb: int = FB, interpret: bool = False) -> jax.Array:
    """Fused multi-forest **range-table** traversal on integer codes
    (``variant="range"``).

    x_q        (B, W)              int32 feature codes at ``frac`` bits
    slot       (B, 1)              int32 forest slot per packet, in [0, F)
    rng_t      (T, F, 3·NI + L)    int32 range rows, tree-major, field-major
                                   columns feat | thresh | mask | payload
                                   (``ops.forest_traverse`` preps this from
                                   the control plane's RangeTables)
    tree_on_t  (T, F, 1)           int32 tree-exists flags
    mode       (F, 1)              int32 vote mode
    Returns    (B, W)              int32 output codes.

    ``B % bb == 0`` (the ops.py wrapper pads).  ``n_entries``/``n_leaves``
    are the static table extents — synthesis-time properties derived from
    the control plane's ``max_nodes``.
    """
    n_batch, width = x_q.shape
    n_trees, n_forests, ncols = rng_t.shape
    if ncols != 3 * n_entries + n_leaves:
        raise ValueError(f"rng_t columns {ncols} != 3*{n_entries} + "
                         f"{n_leaves}")
    if n_batch % bb:
        raise ValueError(f"batch {n_batch} not a multiple of tile {bb}; "
                         "use ops.forest_traverse, which pads")
    grid = (n_batch // bb,)
    return pl.pallas_call(
        functools.partial(_range_kernel, n_trees=n_trees,
                          n_entries=n_entries, n_leaves=n_leaves, frac=frac),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, width), lambda i: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i: (i, 0)),
            pl.BlockSpec((n_trees, n_forests, ncols), lambda i: (0, 0, 0)),
            pl.BlockSpec((n_trees, n_forests, 1), lambda i: (0, 0, 0)),
            pl.BlockSpec((n_forests, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bb, width), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_batch, width), jnp.int32),
        interpret=interpret,
    )(x_q, slot, rng_t, tree_on_t, mode)
