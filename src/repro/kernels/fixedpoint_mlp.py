"""Pallas TPU kernel: fused multi-model fixed-point MLP (the whole data plane
compute stage in one kernel).

The batched data plane (core/inference.py) serves a *mixed-model* packet
batch: every packet carries a Model ID resolved to a table slot, and the
forward pass must use that packet's own weights.  The naive formulation
gathers per-packet weight tensors — ``w[slot]`` materializes ``(B, L, W, W)``
codes, i.e. ``L·W²`` table bytes *per packet* of HBM traffic, then runs one
``einsum`` + one activation round-trip per layer.

This kernel instead keeps the **stacked** tables (all ``M`` models) resident
in VMEM — at paper scale the whole match-action RAM is ~128 KiB, smaller than
one activation tile — and folds the Model-ID dispatch into the GEMM itself:

    z[p, (m·W+i)] = onehot[p, m] · x[p, i]          (mask, VPU)
    acc[p, j]     = Σ_{m,i} z[p, (m·W+i)] · w[l, (m·W+i), j]   (MXU)

Summing over the fused ``(model, feature)`` axis computes, for every packet,
exactly its own model's layer — other models' terms are zeroed by the mask —
so ``M`` interleaved models cost one ``(B, M·W) × (M·W, W)`` contraction per
layer instead of ``B`` gathered vector-matrix products.  Bias add, the
rounding-shift requantize and the opcode-selected activation (ReLU / leaky /
Taylor-sigmoid Horner / hard-sigmoid) all happen on the accumulator tile
while it is still in VMEM: the full ``L``-layer loop touches HBM once for
the packet tile in and once for the result out.

The TPU's matrix unit has no int32×int32 product, so the contraction runs
on narrow operands whose sums are provably exact:

  * ``"int8"`` lane — codes and weights already fit int8, so the masked GEMM
    is one int8×int8→int32 dot (every product ≤ 2^14 in magnitude, and the
    mask leaves W nonzero terms per row).
  * ``"int16"`` lane — both operands split into base-256 digit planes
    (:func:`byte_planes`): low planes are unsigned bytes, the top plane the
    signed remainder, so every plane value is an integer of at most 8
    significant bits — exact in bf16.  Each plane pair is one bf16 dot with
    f32 accumulation; its W nonzero products are each < 2^16, so every sum
    stays below 2^21 and is exact in f32.  The int32 result is
    ``Σ_{i+j<4} dot(x_i, w_j) << 8(i+j)`` — pairs with ``i+j ≥ 4`` shift
    past bit 31 and vanish mod 2^32, which is exactly the int32 wraparound
    of the oracle's dot.

The per-model bias, opcode and layer-on rows are picked by a VPU select
chain (``ref.slot_select``), not a dot.

Integer discipline matches the P4/FPGA pipeline bit-for-bit: int32
accumulation, biases pre-shifted to ``2·frac`` bits, rounding arithmetic
shifts (ties away from zero), Taylor constants as immediates.

Off-TPU the kernel runs under the Pallas interpreter (bit-exact with the
jnp oracle ``ref.fused_mlp_ref``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# The integer semantics (rounding shift, opcode-gated activation, lane
# saturation, slot dispatch) live in exactly one place — ref.py — and are
# traced into the kernel from there, so the kernel/oracle bit-exact
# contract cannot drift.
from .ref import (_select_activation_ref, lane_clamp, rounding_rshift,
                  slot_select)

__all__ = ["fixedpoint_mlp_pallas", "byte_planes", "BB", "KERNEL_VARIANTS"]

# Weight-lane variants of the fused kernel:
#   * "int16" — weights encoded at up to 16 bits, feature codes over the
#     full int32 range; the masked GEMM runs as bf16 digit-plane dots.
#   * "int8"  — the int8 weight-lane (v5e MXU native rate): weights are int8
#     codes, feature codes are saturated into the int8 lane at entry and
#     after every layer's requantize+activation, and the layer dot is an
#     int8×int8→int32 contraction.  Bit-exact against
#     ``ref.fused_mlp_ref(..., lane_bits=8)``.
KERNEL_VARIANTS = ("int16", "int8")

# Batch-tile rows per grid step.  The lane-dim (table width W) rides along
# unpadded: at paper scale W ≤ 32 and the whole working set is VMEM-tiny.
BB = 256

# Digit planes of the int32 feature codes in the "int16" lane.
_X_PLANES = 4


def byte_planes(v: jax.Array, n: int) -> list:
    """Split integer codes into ``n`` base-256 digit planes as bf16:
    ``v == Σ_k plane_k · 256^k`` with planes ``0..n-2`` the unsigned bytes
    ``(v >> 8k) & 255`` and plane ``n-1`` the signed remainder
    ``v >> 8(n-1)``.  ``n`` must cover ``v``'s bit width (4 for int32, 2
    for int16 codes); every plane value then has at most 8 significant bits
    and converts to bf16 exactly."""
    v = v.astype(jnp.int32)
    planes = [(v >> (8 * k)) & 255 for k in range(n - 1)]
    planes.append(v >> (8 * (n - 1)))
    return [p.astype(jnp.float32).astype(jnp.bfloat16) for p in planes]


def _dot(a: jax.Array, b: jax.Array, out_dtype) -> jax.Array:
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=out_dtype)


def _kernel(x_ref, slot_ref, w_ref, b_ref, act_ref, on_ref, o_ref, *,
            n_layers: int, n_models: int, frac: int, sig_coeffs: tuple,
            leaky_alpha_q: int, variant: str):
    x = x_ref[...]  # (bb, W) int32 feature codes
    slot = slot_ref[...]  # (bb, 1) int32, pre-clamped to [0, M)
    bb, width = x.shape
    lane_bits = 8 if variant == "int8" else None

    m_iota = jax.lax.broadcasted_iota(jnp.int32, (bb, n_models), 1)
    onehot = (slot == m_iota).astype(jnp.int32)  # (bb, M)

    def layer(l, x):  # one layer per step: the unrolled body stays small
        # Model-ID dispatch fused into the GEMM: mask, then contract the
        # combined (model, feature) axis against the stacked layer table.
        z = (onehot[:, :, None] * x[:, None, :]).reshape(bb, n_models * width)
        if variant == "int8":
            # the saturated codes fit int8 exactly, so narrowing both dot
            # operands is lossless (w_ref already carries int8 codes)
            acc = _dot(z.astype(jnp.int8), w_ref[l], jnp.int32)
        else:
            # w_ref: (n_w, L, M·W, W) bf16 weight digit planes
            n_w = w_ref.shape[0]
            zp = byte_planes(z, _X_PLANES)
            acc = jnp.zeros((bb, width), jnp.int32)
            for s in range(_X_PLANES):  # digit-pair weight 256^s
                ys = jnp.zeros((bb, width), jnp.int32)
                for i in range(s + 1):
                    if s - i < n_w:
                        ys = ys + _dot(zp[i], w_ref[s - i, l],
                                       jnp.float32).astype(jnp.int32)
                acc = acc + (ys << (8 * s))
        acc = acc + slot_select(slot, b_ref[l])
        y = rounding_rshift(acc, frac)  # 2·frac-bit accumulator → frac bits
        y = _select_activation_ref(y, slot_select(slot, act_ref[l]),
                                   frac=frac, sig_coeffs=sig_coeffs,
                                   leaky_alpha_q=leaky_alpha_q)
        y = lane_clamp(y, lane_bits)
        on = slot_select(slot, on_ref[l]) > 0
        return jnp.where(on, y, x)  # inactive layer: identity (padded depth)

    o_ref[...] = jax.lax.fori_loop(0, n_layers, layer,
                                   lane_clamp(x, lane_bits))


@functools.partial(jax.jit, static_argnames=("frac", "sig_coeffs",
                                             "leaky_alpha_q", "bb",
                                             "variant", "interpret"))
def fixedpoint_mlp_pallas(x_q: jax.Array, slot: jax.Array, w: jax.Array,
                          b: jax.Array, act: jax.Array, layer_on: jax.Array,
                          *, frac: int, sig_coeffs: tuple,
                          leaky_alpha_q: int, bb: int = BB,
                          variant: str = "int16",
                          interpret: bool = False) -> jax.Array:
    """Fused multi-model MLP forward on integer codes.

    x_q       (B, W)        int32 feature codes at ``frac`` fractional bits
    slot      (B, 1)        int32 table slot per packet, in ``[0, M)``
    w         (L, M·W, W)   stacked weight codes, layer-major — any integer
                            dtype for ``variant="int16"`` (its width fixes
                            the number of digit planes), int8 for
                            ``variant="int8"``
    b         (L, M, W)     int32 bias codes at ``2·frac`` bits
    act       (L, M, 1)     int32 activation opcodes
    layer_on  (L, M, 1)     int32 layer-exists flags
    Returns   (B, W)        int32 output codes at ``frac`` bits.

    ``B % bb == 0`` (the ops.py wrapper pads).  The tables ride whole into
    VMEM each grid step (M·L·W² ≤ a few hundred KiB at paper scale).
    """
    if variant not in KERNEL_VARIANTS:
        raise ValueError(f"unknown kernel variant: {variant!r}")
    n_batch, width = x_q.shape
    n_layers, mw, _ = w.shape
    n_models = mw // width
    if n_batch % bb:
        # a floor-divided grid would silently leave the tail rows unwritten
        raise ValueError(f"batch {n_batch} not a multiple of tile {bb}; "
                         "use ops.fused_mlp, which pads")
    if variant == "int8":
        w_spec = pl.BlockSpec((n_layers, mw, width), lambda i: (0, 0, 0))
    else:
        # table-sized digit split, outside the kernel
        w = jnp.stack(byte_planes(w, jnp.dtype(w.dtype).itemsize))
        w_spec = pl.BlockSpec(w.shape, lambda i: (0, 0, 0, 0))
    grid = (n_batch // bb,)
    return pl.pallas_call(
        functools.partial(_kernel, n_layers=n_layers, n_models=n_models,
                          frac=frac,
                          sig_coeffs=tuple(int(c) for c in sig_coeffs),
                          leaky_alpha_q=leaky_alpha_q, variant=variant),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, width), lambda i: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i: (i, 0)),
            w_spec,
            pl.BlockSpec((n_layers, n_models, width), lambda i: (0, 0, 0)),
            pl.BlockSpec((n_layers, n_models, 1), lambda i: (0, 0, 0)),
            pl.BlockSpec((n_layers, n_models, 1), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bb, width), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_batch, width), jnp.int32),
        interpret=interpret,
    )(x_q, slot, w, b, act, layer_on)
