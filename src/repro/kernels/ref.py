"""Pure-jnp oracles for every Pallas kernel (the BMv2-simulation analogue:
bit-faithful reference semantics the hardware kernels must reproduce)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["fixedpoint_matmul_ref", "taylor_activation_ref", "fused_mlp_ref",
           "fused_mlp_gather_ref", "rounding_rshift", "lane_clamp",
           "slot_select",
           "wkv_scan_ref", "forest_traverse_numpy", "forest_traverse_ref",
           "forest_traverse_gather_ref", "forest_range_ref",
           "forest_range_gather_ref", "FOREST_REGRESS", "FOREST_CLASSIFY",
           "flow_update_numpy", "rounding_rshift_np", "sat_shl_np",
           "N_FLOW_REGISTERS", "N_FLOW_FEATURES", "FLOW_CODE_MAX",
           "REG_PKT_COUNT", "REG_BYTE_COUNT", "REG_LAST_TS", "REG_FIRST_TS",
           "REG_EWMA_IAT", "REG_EWMA_LEN", "REG_MIN_LEN", "REG_MAX_LEN",
           "FLOW_FEATURE_NAMES"]


def wkv_scan_ref(a: jax.Array, b: jax.Array, v: jax.Array, tot: jax.Array,
                 diag: jax.Array) -> jax.Array:
    """Oracle for the WKV chunk-scan kernel: sequential chunks per (B·H) row.

    a/b/v: (BH, NC, C, D); tot: (BH, NC, 1, D); diag: (BH, NC, C, 1).
    """
    bh, nc, c, d = a.shape
    tri = jnp.tril(jnp.ones((c, c), jnp.float32), k=-1)

    def per_row(a_r, b_r, v_r, tot_r, diag_r):
        def step(s0, inp):
            a_c, b_c, v_c, tot_c, diag_c = inp
            scores = (a_c @ b_c.T) * tri
            o = scores @ v_c + diag_c * v_c + a_c @ s0
            s_new = s0 * tot_c.T + (b_c * tot_c).T @ v_c
            return s_new, o

        s0 = jnp.zeros((d, d), jnp.float32)
        _, outs = jax.lax.scan(step, s0, (a_r, b_r, v_r, tot_r, diag_r))
        return outs

    return jax.vmap(per_row)(a, b, v, tot, diag)


def rounding_rshift(x: jax.Array, shift: int) -> jax.Array:
    """Arithmetic right shift, round-to-nearest, ties away from zero (the
    requantization primitive — identical to core.fixedpoint)."""
    if shift <= 0:
        return x
    rounding = jnp.where(x >= 0, 1 << (shift - 1), (1 << (shift - 1)) - 1
                         ).astype(x.dtype)
    return jnp.right_shift(x + rounding, shift)


def lane_clamp(x: jax.Array, lane_bits: int | None) -> jax.Array:
    """Saturate codes into a ``lane_bits``-wide signed lane (the int8
    weight-lane variant's requantize boundary); identity when ``None``."""
    if lane_bits is None:
        return x
    hi = (1 << (lane_bits - 1)) - 1
    return jnp.clip(x, -hi - 1, hi)


def fixedpoint_matmul_ref(x_codes: jax.Array, w_codes: jax.Array,
                          x_scale: jax.Array, w_scale: jax.Array,
                          bias: jax.Array | None = None) -> jax.Array:
    """W8A8 GEMM oracle: int8×int8 → int32 accumulate → float rescale.

    x_codes: (M, K) int8, per-row scale (M, 1) float32.
    w_codes: (K, N) int8, per-column scale (1, N) float32.
    Returns float32 (M, N): ``acc * x_scale * w_scale (+ bias)``.
    """
    acc = jax.lax.dot_general(
        x_codes, w_codes, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * x_scale * w_scale
    if bias is not None:
        out = out + bias
    return out


def slot_select(slot: jax.Array, table: jax.Array) -> jax.Array:
    """Per-packet table row ``table[slot]`` as a chain of two-arm selects
    over the table's ``K`` rows — the slot dispatch of the Pallas kernels.

    slot (B, 1) int32 in ``[0, K)`` · table (K, C) → (B, C).  Exact for
    any integer table (no products are formed), and it lowers to plain VPU
    selects: the TPU's matrix unit has no int32×int32 dot to do a one-hot
    contraction with."""
    out = jnp.zeros((slot.shape[0], table.shape[1]), table.dtype)
    for k in range(table.shape[0]):  # static: K is a synthesis-time bound
        out = jnp.where(slot == k, table[k:k + 1, :], out)
    return out


def _select_activation_ref(y: jax.Array, opcode: jax.Array, *, frac: int,
                           sig_coeffs, leaky_alpha_q: int) -> jax.Array:
    """Opcode-gated integer activation (opcodes as in core.control_plane:
    1=relu, 2=taylor-sigmoid, 3=leaky-relu, 4=hard-sigmoid; anything else
    is the identity).

    All five arms are computed unconditionally (they are cheap VPU
    elementwise chains; per-packet opcodes make real branching impossible
    anyway) and a chain of two-arm selects picks each lane's arm.  The one
    definition is shared by the Pallas kernel and both jnp oracles, so the
    selection can never split the bit-exactness contract (Mosaic lowers
    only two-arm selects).
    """
    relu = jnp.maximum(y, 0)
    leaky = jnp.where(y > 0, y,
                      rounding_rshift(y * jnp.int32(leaky_alpha_q), frac))
    xc = jnp.clip(y, -(1 << 14), 1 << 14)
    sig = jnp.full(y.shape, int(sig_coeffs[-1]), jnp.int32)
    for c in sig_coeffs[-2::-1]:
        sig = rounding_rshift(sig * xc, frac) + jnp.int32(int(c))
    half = jnp.int32(1 << (frac - 1))
    one = jnp.int32(1 << frac)
    hsig = jnp.clip(half + rounding_rshift(y, 2), 0, one)
    out = y
    out = jnp.where(opcode == 1, relu, out)
    out = jnp.where(opcode == 2, sig, out)
    out = jnp.where(opcode == 3, leaky, out)
    out = jnp.where(opcode == 4, hsig, out)
    return out


def fused_mlp_ref(x_q: jax.Array, slot: jax.Array, w: jax.Array, b: jax.Array,
                  act: jax.Array, layer_on: jax.Array, *, frac: int,
                  sig_coeffs, leaky_alpha_q: int,
                  lane_bits: int | None = None) -> jax.Array:
    """Oracle for the fused multi-model MLP kernel — the same masked-GEMM
    formulation as one int32 dot per layer in plain jnp (the kernel splits
    it into exact narrow dots).  This is the *cross-check* path
    (``backend="ref"``): the production CPU lowering is
    :func:`fused_mlp_gather_ref` below (XLA:CPU scalarizes wide s32 GEMMs,
    so the gathered batched-matvec form wins there; ``ops.fused_mlp``
    selects it for ``backend="auto"`` off-TPU).

    Shapes as in ``fixedpoint_mlp_pallas``: x_q (B, W) int32; slot (B, 1)
    int32 in [0, M); w (L, M·W, W) int32; b (L, M, W) int32; act/layer_on
    (L, M, 1) int32.

    ``lane_bits=8`` is the **int8 weight-lane** contract: feature codes are
    saturated into the int8 lane on entry and after every layer's
    requantize+activation, and weight codes are assumed to already fit int8
    (the control plane's ``weight_bits=8`` format).  The arithmetic below is
    int32 throughout, which is bit-identical to an int8×int8→int32 MXU dot
    over the same saturated values — that is the oracle the Pallas
    ``variant="int8"`` kernel must reproduce.
    """
    n_batch, width = x_q.shape
    n_layers, mw, _ = w.shape
    n_models = mw // width
    onehot = (slot == jnp.arange(n_models, dtype=jnp.int32)[None, :]
              ).astype(jnp.int32)  # (B, M)
    x = lane_clamp(x_q, lane_bits)
    for l in range(n_layers):
        z = (onehot[:, :, None] * x[:, None, :]).reshape(n_batch, mw)
        acc = jax.lax.dot_general(z, w[l], (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        acc = acc + jax.lax.dot_general(onehot, b[l], (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.int32)
        y = rounding_rshift(acc, frac)
        opcode = jax.lax.dot_general(onehot, act[l], (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.int32)
        y = _select_activation_ref(y, opcode, frac=frac,
                                   sig_coeffs=sig_coeffs,
                                   leaky_alpha_q=leaky_alpha_q)
        y = lane_clamp(y, lane_bits)
        on = jax.lax.dot_general(onehot, layer_on[l],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.int32) > 0
        x = jnp.where(on, y, x)
    return x


def fused_mlp_gather_ref(x_q: jax.Array, slot: jax.Array, w: jax.Array,
                         b: jax.Array, act: jax.Array, layer_on: jax.Array,
                         *, frac: int, sig_coeffs,
                         leaky_alpha_q: int,
                         lane_bits: int | None = None) -> jax.Array:
    """Bit-identical CPU realization of the fused MLP: per-packet table
    gather + int32 batched matvec (``bi,bij->bj``), which XLA:CPU vectorizes,
    unlike wide s32 GEMMs.  Tables in control-plane layout: w (M, L, W, W),
    b (M, L, W), act/layer_on (M, L); slot (B,).  ``lane_bits`` selects the
    saturating weight-lane variant (see :func:`fused_mlp_ref`)."""
    wg = w[slot]          # (B, L, W, W)
    bg = b[slot]          # (B, L, W)
    ag = act[slot]        # (B, L)
    og = layer_on[slot]   # (B, L)
    n_layers = w.shape[1]
    x = lane_clamp(x_q, lane_bits)
    for l in range(n_layers):
        acc = jnp.einsum("bi,bij->bj", x, wg[:, l].astype(jnp.int32),
                         preferred_element_type=jnp.int32) + bg[:, l]
        y = rounding_rshift(acc, frac)
        y = _select_activation_ref(y, ag[:, l][:, None], frac=frac,
                                   sig_coeffs=sig_coeffs,
                                   leaky_alpha_q=leaky_alpha_q)
        y = lane_clamp(y, lane_bits)
        x = jnp.where(og[:, l][:, None] > 0, y, x)
    return x


# ---------------------------------------------------------------------------
# Tree-ensemble traversal (repro.forest) — three realizations of one contract
# ---------------------------------------------------------------------------

# Forest vote modes, stored per forest slot in the control-plane tables.
FOREST_REGRESS = 0   # output lane 0 = Σ_t leaf codes (pre-divided by n_trees)
FOREST_CLASSIFY = 1  # output lane c = (1 << frac) per tree voting class c

# Node-table field order inside the packed (…, 5) axis:
#   0 feature index · 1 quantized threshold · 2 left child · 3 right child ·
#   4 leaf payload (class index / pre-divided value code).
# Leaves self-loop (left == right == self), so a level-bounded traversal of
# ``max_depth`` steps always lands on a leaf without a per-step leaf test.


def forest_traverse_numpy(x_q: np.ndarray, slot: np.ndarray,
                          nodes: np.ndarray, tree_on: np.ndarray,
                          mode: np.ndarray, *, max_depth: int,
                          frac: int) -> np.ndarray:
    """THE forest oracle: per-packet pure-Python walk of the packed tables.

    This is deliberately scalar (three nested Python loops following child
    pointers node by node) so nothing about the vectorized formulations can
    leak into the reference semantics.  Every lowering — the masked jnp form,
    the gathered batched form, and the Pallas kernel — must reproduce it
    bit for bit.

    x_q (B, W) int32 feature codes · slot (B,) int32 forest slots ·
    nodes (F, T, N, 5) int32 (field order above) · tree_on (F, T) int32 ·
    mode (F,) int32 — returns (B, W) int32 output codes.
    """
    x_q = np.asarray(x_q)
    slot = np.asarray(slot).reshape(-1)
    nodes = np.asarray(nodes)
    tree_on = np.asarray(tree_on)
    mode = np.asarray(mode)
    n_batch, width = x_q.shape
    _, n_trees, _, _ = nodes.shape
    out = np.zeros((n_batch, width), np.int32)
    one_q = np.int32(1 << frac)
    for p in range(n_batch):
        f = int(slot[p])
        for t in range(n_trees):
            if not tree_on[f, t]:
                continue
            cur = 0
            for _ in range(max_depth):
                feat = int(nodes[f, t, cur, 0])
                if x_q[p, feat] <= nodes[f, t, cur, 1]:
                    cur = int(nodes[f, t, cur, 2])
                else:
                    cur = int(nodes[f, t, cur, 3])
            leaf = nodes[f, t, cur, 4]
            if mode[f] == FOREST_CLASSIFY:
                out[p, int(leaf)] += one_q
            else:
                out[p, 0] += leaf
    return out


def forest_traverse_ref(x_q: jax.Array, slot: jax.Array, nodes_t: jax.Array,
                        tree_on_t: jax.Array, mode: jax.Array, *,
                        max_depth: int, frac: int) -> jax.Array:
    """Masked (one-hot) jnp oracle for the Pallas traversal kernel — the
    kernel's formulation in plain int32 jnp.

    Kernel layout (see ``ops.forest_traverse`` for the prep):
      x_q (B, W) int32 · slot (B, 1) int32 in [0, F) ·
      nodes_t (T, F, 5·N) int32 tree-major with field-major columns
      (``nodes_t[t, f, field·N + n]``) · tree_on_t (T, F, 1) int32 ·
      mode (F, 1) int32.  Returns (B, W) int32.

    The per-packet forest select is one (B, F) one-hot int32 dot per tree
    (gathering that tree's whole node table for every packet — the kernel
    picks the same rows with :func:`slot_select`); the per-step node/feature
    selects are iota-compare row reductions, as in the kernel.
    """
    n_batch, width = x_q.shape
    n_trees, n_forests, ncols = nodes_t.shape
    n_nodes = ncols // 5
    f_iota = jnp.arange(n_forests, dtype=jnp.int32)[None, :]
    onehot_f = (slot == f_iota).astype(jnp.int32)  # (B, F)
    mode_p = jax.lax.dot_general(onehot_f, mode, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.int32)  # (B, 1)
    n_iota = jnp.arange(n_nodes, dtype=jnp.int32)[None, :]
    w_iota = jnp.arange(width, dtype=jnp.int32)[None, :]
    one_q = jnp.int32(1 << frac)
    acc = jnp.zeros((n_batch, width), jnp.int32)
    for t in range(n_trees):
        tbl = jax.lax.dot_general(onehot_f, nodes_t[t],
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        feat_t = tbl[:, 0 * n_nodes: 1 * n_nodes]
        th_t = tbl[:, 1 * n_nodes: 2 * n_nodes]
        left_t = tbl[:, 2 * n_nodes: 3 * n_nodes]
        right_t = tbl[:, 3 * n_nodes: 4 * n_nodes]
        leaf_t = tbl[:, 4 * n_nodes: 5 * n_nodes]
        on = jax.lax.dot_general(onehot_f, tree_on_t[t],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.int32) > 0
        cur = jnp.zeros((n_batch, 1), jnp.int32)
        for _ in range(max_depth):
            sel = (n_iota == cur).astype(jnp.int32)  # (B, N)
            feat = jnp.sum(sel * feat_t, axis=1, keepdims=True)
            th = jnp.sum(sel * th_t, axis=1, keepdims=True)
            lf = jnp.sum(sel * left_t, axis=1, keepdims=True)
            rt = jnp.sum(sel * right_t, axis=1, keepdims=True)
            xv = jnp.sum(jnp.where(w_iota == feat, x_q, 0), axis=1,
                         keepdims=True)
            cur = jnp.where(xv <= th, lf, rt)
        sel = (n_iota == cur).astype(jnp.int32)
        leaf = jnp.sum(sel * leaf_t, axis=1, keepdims=True)  # (B, 1)
        vote_cls = jnp.where(w_iota == leaf, one_q, 0)
        vote_reg = jnp.where(w_iota == 0, leaf, 0)
        contrib = jnp.where(mode_p == FOREST_CLASSIFY, vote_cls, vote_reg)
        acc = acc + jnp.where(on, contrib, 0)
    return acc


def forest_traverse_gather_ref(x_q: jax.Array, slot: jax.Array,
                               nodes: jax.Array, tree_on: jax.Array,
                               mode: jax.Array, *, max_depth: int,
                               frac: int) -> jax.Array:
    """Bit-identical CPU realization: direct per-step table indexing (each
    step gathers only the (B, T) records actually visited — never a
    per-packet copy of the whole table) with the pointer fields packed into
    one **meta word** per node, ``feat<<20 | left<<10 | right``, so a
    traversal step costs three (B, T)-sized gathers (meta, threshold, split
    feature) instead of five.  The packing is pure integer re-coding of
    in-range fields (children < N ≤ 1024, feature < width ≤ 2048 — the
    control plane validates both), so unpacking by shift/mask is exact and
    the step remains bit-identical to the scalar oracle.  XLA:CPU
    vectorizes these gathers; the masked one-hot form's wide s32 dots
    scalarize there, like the MLP's.

    Tables in control-plane layout: nodes (F, T, N, 5), tree_on (F, T),
    mode (F,); slot (B,) int32.  Returns (B, W) int32.
    """
    n_batch, width = x_q.shape
    _, n_trees, n_nodes, _ = nodes.shape
    if n_nodes > 1024 or width > 2048:
        raise ValueError(
            f"meta-word packing bound exceeded (n_nodes={n_nodes} > 1024 "
            f"or width={width} > 2048) — beyond any paper-scale table")
    # table-sized (not batch-sized) packing work, traced per call like the
    # MLP wrapper's layout transposes
    meta = (nodes[..., 0] << 20) | (nodes[..., 2] << 10) | nodes[..., 3]
    th_t = nodes[..., 1]
    leaf_t = nodes[..., 4]
    sl = slot[:, None]                  # (B, 1)
    tr = jnp.arange(n_trees, dtype=jnp.int32)[None, :]
    on = tree_on[slot] > 0              # (B, T)
    md = mode[slot][:, None]            # (B, 1)
    rows = jnp.arange(n_batch)[:, None]
    cur = jnp.zeros((n_batch, n_trees), jnp.int32)
    for _ in range(max_depth):
        m = meta[sl, tr, cur]           # (B, T) packed feat|left|right
        th = th_t[sl, tr, cur]
        xv = x_q[rows, m >> 20]
        cur = jnp.where(xv <= th, (m >> 10) & 1023, m & 1023)
    leaf = leaf_t[sl, tr, cur]          # (B, T)
    one_q = jnp.int32(1 << frac)
    lane = jnp.arange(width, dtype=jnp.int32)[None, None, :]
    votes = jnp.sum(jnp.where((leaf[:, :, None] == lane) & on[:, :, None],
                              one_q, 0), axis=1)         # (B, W)
    reg = jnp.sum(jnp.where(on, leaf, 0), axis=1)        # (B,)
    reg_out = jnp.where(lane[0] == 0, reg[:, None], 0)
    return jnp.where(md == FOREST_CLASSIFY, votes, reg_out)


def _forest_vote(leaf: jax.Array, on: jax.Array, md: jax.Array, width: int,
                 frac: int) -> jax.Array:
    """Shared vote accumulation over per-tree exit leaves: classify forests
    one-hot their leaf's class lane with ``1 << frac`` per live tree,
    regress forests sum pre-divided leaf codes into lane 0.  ``leaf``/``on``
    are (B, T); ``md`` is (B, 1)."""
    one_q = jnp.int32(1 << frac)
    lane = jnp.arange(width, dtype=jnp.int32)[None, None, :]
    votes = jnp.sum(jnp.where((leaf[:, :, None] == lane) & on[:, :, None],
                              one_q, 0), axis=1)         # (B, W)
    reg = jnp.sum(jnp.where(on, leaf, 0), axis=1)        # (B,)
    reg_out = jnp.where(lane[0] == 0, reg[:, None], 0)
    return jnp.where(md == FOREST_CLASSIFY, votes, reg_out)


def forest_range_gather_ref(x_q: jax.Array, slot: jax.Array,
                            feat: jax.Array, thresh: jax.Array,
                            lmask: jax.Array, payload: jax.Array,
                            tree_on: jax.Array, mode: jax.Array, *,
                            frac: int) -> jax.Array:
    """CPU realization of the **range-table** forest lane (``variant=
    "range"`` — the pForest ternary-match lowering compiled by
    ``repro.forest.ranges``).

    Per tree, every range entry's comparison ``x[feat] <= thresh`` is
    evaluated at once (pure vectorized compare — no step-by-step gather
    chain), the surviving-leaf masks of the *failed* comparisons AND-reduce
    into one word, and the exit leaf is the lowest set bit (in-order leaf
    numbering).  Bit-exact against ``forest_traverse_numpy`` on every
    well-formed tree: the comparisons are the identical quantized-code
    compares the pointer chase performs, just evaluated in parallel.

    Tables in control-plane layout: feat/thresh (F, T, NI) int32, lmask
    (F, T, NI) uint32, payload (F, T, L) int32, tree_on (F, T), mode (F,);
    slot (B,) int32.  Returns (B, W) int32.
    """
    n_batch, width = x_q.shape
    fg = feat[slot]                      # (B, T, NI)
    tg = thresh[slot]                    # (B, T, NI)
    mg = lmask[slot]                     # (B, T, NI) uint32
    n_trees, ni = fg.shape[1], fg.shape[2]
    xv = jnp.take_along_axis(
        x_q[:, None, :], fg.reshape(n_batch, 1, n_trees * ni),
        axis=2).reshape(fg.shape)
    cond = xv <= tg
    terms = jnp.where(cond, jnp.uint32(0xFFFFFFFF), mg)
    word = terms[:, :, 0]
    for i in range(1, ni):               # static NI: unrolled AND-reduce
        word = word & terms[:, :, i]
    iso = word & (~word + jnp.uint32(1))            # lowest set bit
    leaf_idx = jax.lax.population_count(iso - jnp.uint32(1)) \
        .astype(jnp.int32)                          # (B, T)
    leaf = jnp.take_along_axis(payload[slot], leaf_idx[:, :, None],
                               axis=2)[..., 0]      # (B, T)
    on = tree_on[slot] > 0
    md = mode[slot][:, None]
    return _forest_vote(leaf, on, md, width, frac)


def forest_range_ref(x_q: jax.Array, slot: jax.Array, rng_t: jax.Array,
                     tree_on_t: jax.Array, mode: jax.Array, *,
                     n_entries: int, n_leaves: int, frac: int) -> jax.Array:
    """Masked (one-hot) jnp oracle for the Pallas range kernel — the
    kernel's formulation in plain int32 jnp, its forest select a one-hot
    dot (the ``backend="ref"`` path of ``variant="range"``, exactly like
    :func:`forest_traverse_ref` for the chase kernel).

    Kernel layout (see ``ops.forest_traverse`` for the prep): rng_t
    ``(T, F, 3·NI + L)`` int32, tree-major with field-major columns
    ``feat | thresh | leaf-mask (uint32 bitcast) | payload``; tree_on_t
    (T, F, 1); mode (F, 1); slot (B, 1).  Returns (B, W) int32.
    """
    n_batch, width = x_q.shape
    n_trees, n_forests, _ = rng_t.shape
    f_iota = jnp.arange(n_forests, dtype=jnp.int32)[None, :]
    onehot_f = (slot == f_iota).astype(jnp.int32)  # (B, F)
    mode_p = jax.lax.dot_general(onehot_f, mode, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.int32)
    w_iota = jnp.arange(width, dtype=jnp.int32)[None, :]
    acc = jnp.zeros((n_batch, width), jnp.int32)
    for t in range(n_trees):
        tbl = jax.lax.dot_general(onehot_f, rng_t[t],
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        feat_t = tbl[:, 0 * n_entries: 1 * n_entries]
        th_t = tbl[:, 1 * n_entries: 2 * n_entries]
        mask_t = tbl[:, 2 * n_entries: 3 * n_entries].astype(jnp.uint32)
        pay_t = tbl[:, 3 * n_entries: 3 * n_entries + n_leaves]
        on = jax.lax.dot_general(onehot_f, tree_on_t[t],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.int32) > 0
        word = jnp.full((n_batch, 1), 0xFFFFFFFF, jnp.uint32)
        for i in range(n_entries):
            fe = feat_t[:, i: i + 1]
            xv = jnp.sum(jnp.where(w_iota == fe, x_q, 0), axis=1,
                         keepdims=True)
            cond = xv <= th_t[:, i: i + 1]
            word = word & jnp.where(cond, jnp.uint32(0xFFFFFFFF),
                                    mask_t[:, i: i + 1])
        iso = word & (~word + jnp.uint32(1))
        bit = (iso - jnp.uint32(1)).astype(jnp.uint32)
        l_iota = jnp.arange(n_leaves, dtype=jnp.uint32)[None, :]
        is_leaf = ((bit >> l_iota) & jnp.uint32(1)).astype(jnp.int32)
        # popcount(iso - 1) as a bit-test dot: leaf_idx = Σ_l bit[l]
        leaf_idx = jnp.sum(is_leaf, axis=1, keepdims=True)  # (B, 1)
        l32 = jnp.arange(n_leaves, dtype=jnp.int32)[None, :]
        leaf = jnp.sum(jnp.where(l32 == leaf_idx, pay_t, 0), axis=1,
                       keepdims=True)                       # (B, 1)
        one_q = jnp.int32(1 << frac)
        vote_cls = jnp.where(w_iota == leaf, one_q, 0)
        vote_reg = jnp.where(w_iota == 0, leaf, 0)
        contrib = jnp.where(mode_p == FOREST_CLASSIFY, vote_cls, vote_reg)
        acc = acc + jnp.where(on, contrib, 0)
    return acc


# ---------------------------------------------------------------------------
# Stateful flow engine (repro.flow) — per-flow register update + feature emit
# ---------------------------------------------------------------------------

# Register-file columns, one row per flow-table slot.  All registers are
# int32; counters/lengths/timestamps are raw integer quantities, the EWMA
# registers are fixed-point codes at the wire's ``frac`` fractional bits
# (the same grid ``core.fixedpoint.encode`` writes).
REG_PKT_COUNT = 0   # packets seen (0 ⇒ slot holds no flow state yet)
REG_BYTE_COUNT = 1  # saturating byte total
REG_LAST_TS = 2     # tick of the last packet (drives inter-arrival + expiry)
REG_FIRST_TS = 3    # tick of the first packet (drives the duration feature)
REG_EWMA_IAT = 4    # EWMA of inter-arrival ticks, code at ``frac``
REG_EWMA_LEN = 5    # EWMA of packet length, code at ``frac``
REG_MIN_LEN = 6     # smallest packet length seen
REG_MAX_LEN = 7     # largest packet length seen
N_FLOW_REGISTERS = 8

# Emitted per-packet feature lanes (post-update flow state, every lane a
# fixed-point code at ``frac`` — directly encodable into the wire's feature
# block).  ``FeatureSpec`` columns index into this order.
FLOW_FEATURE_NAMES = ("pkt_count", "byte_count", "iat_ewma", "len_ewma",
                      "len_min", "len_max", "duration", "cms_count")
N_FLOW_FEATURES = len(FLOW_FEATURE_NAMES)

# Every register/feature value lives in [0, FLOW_CODE_MAX] (EWMA deltas then
# fit int32 with headroom), so the update arithmetic can never wrap — the
# saturation bound is part of the bit-exact contract, not a soft limit.
FLOW_CODE_MAX = (1 << 30) - 1


def rounding_rshift_np(x, shift: int):
    """Numpy twin of :func:`rounding_rshift` (arithmetic right shift,
    round-to-nearest, ties away from zero) — the oracle and the vectorized
    CPU lowering must share one definition with the jnp kernels."""
    if shift <= 0:
        return x
    x = np.asarray(x)
    rounding = np.where(x >= 0, 1 << (shift - 1), (1 << (shift - 1)) - 1)
    return (x + rounding.astype(x.dtype)) >> shift


def sat_shl_np(v, shift: int):
    """Saturating left shift of a non-negative quantity onto the ``shift``
    fractional-bit code grid: values beyond ``FLOW_CODE_MAX >> shift``
    saturate instead of wrapping."""
    v = np.minimum(np.maximum(v, 0), FLOW_CODE_MAX >> shift)
    return v << shift


def flow_update_numpy(state: np.ndarray, cms: np.ndarray, slots: np.ndarray,
                      cells: np.ndarray, ts: np.ndarray, length: np.ndarray,
                      live: np.ndarray, *, frac: int, ewma_shift: int,
                      byte_shift: int, dur_shift: int):
    """THE flow-update oracle: a pure-Python per-packet walk of the register
    file, in batch order.

    Deliberately scalar (the hardware analogue is one packet at a time
    through the stateful ALU) so nothing about the vectorized formulations
    can leak into the reference semantics; the Pallas kernel and the
    rank-round CPU lowering (``kernels.flow_update``) must reproduce it bit
    for bit — including the saturation bounds and the rounding-shift EWMA.

    state  (S, N_FLOW_REGISTERS) int32 — per-slot register rows
    cms    (D, Wc) int32 — count-min sketch counters
    slots  (B,) int32 — flow-table slot per packet (resolved by FlowTable)
    cells  (B, D) int32 — count-min cell per packet per sketch row
    ts     (B,) int32 — arrival tick; length (B,) int32 — wire bytes
    live   (B,) bool/int — 0 rows are padding: no state touch, zero features

    Returns ``(new_state, new_cms, features)`` with ``features`` of shape
    ``(B, N_FLOW_FEATURES)`` int32 codes at ``frac`` — the **post-update**
    flow state as each packet observed it, which is what a per-packet
    stateful P4 pipeline exports to its ML stage.
    """
    state = np.array(state, np.int32, copy=True)
    cms = np.array(cms, np.int32, copy=True)
    slots = np.asarray(slots).reshape(-1)
    n = slots.shape[0]
    depth = cms.shape[0]
    feats = np.zeros((n, N_FLOW_FEATURES), np.int32)

    def _shl(v, s=frac):
        return int(sat_shl_np(int(v), s))

    for p in range(n):
        if not live[p]:
            continue
        s = int(slots[p])
        t = int(ts[p])
        ln = max(int(length[p]), 0)
        row = state[s]
        cnt = int(row[REG_PKT_COUNT])
        len_q = _shl(ln)
        if cnt == 0:  # fresh slot: this packet opens the flow
            first = t
            iat_e = 0
            len_e = len_q
            mn = mx = ln
            byte = min(ln, FLOW_CODE_MAX)
            cnt2 = 1
        else:
            iat_q = _shl(max(t - int(row[REG_LAST_TS]), 0))
            if cnt == 1:  # first inter-arrival sample seeds the EWMA
                iat_e = iat_q
            else:
                iat_e = int(row[REG_EWMA_IAT]) + int(rounding_rshift_np(
                    np.int64(iat_q - int(row[REG_EWMA_IAT])), ewma_shift))
            len_e = int(row[REG_EWMA_LEN]) + int(rounding_rshift_np(
                np.int64(len_q - int(row[REG_EWMA_LEN])), ewma_shift))
            mn = min(int(row[REG_MIN_LEN]), ln)
            mx = max(int(row[REG_MAX_LEN]), ln)
            byte = min(int(row[REG_BYTE_COUNT]) + ln, FLOW_CODE_MAX)
            cnt2 = min(cnt + 1, FLOW_CODE_MAX)
            first = int(row[REG_FIRST_TS])
        state[s] = (cnt2, byte, t, first, iat_e, len_e, mn, mx)
        est = FLOW_CODE_MAX
        for d in range(depth):
            c = int(cells[p, d])
            cms[d, c] = min(int(cms[d, c]) + 1, FLOW_CODE_MAX)
            est = min(est, int(cms[d, c]))
        feats[p] = (_shl(cnt2), _shl(byte >> byte_shift), iat_e, len_e,
                    _shl(mn), _shl(mx), _shl(max(t - first, 0) >> dur_shift),
                    _shl(est))
    return state, cms, feats


def taylor_activation_ref(x_q: jax.Array, coeffs_q: np.ndarray,
                          x_frac: int) -> jax.Array:
    """Integer Horner oracle (paper Table 3 × Table 4 pipeline).

    x_q: int32 codes with ``x_frac`` fractional bits (pre-clamped to ±2^14 by
    the wrapper); ``coeffs_q``: ascending int codes at the coefficient scale.
    Returns int32 codes at the coefficient scale.
    """
    x_q = x_q.astype(jnp.int32)
    acc = jnp.full(x_q.shape, int(coeffs_q[-1]), jnp.int32)
    for c in coeffs_q[-2::-1]:
        acc = rounding_rshift(acc * x_q, x_frac) + jnp.int32(int(c))
    return acc
