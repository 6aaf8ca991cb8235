"""The serving compute stage as **one device dispatch** per staged batch.

:func:`serve_lanes` is the lane-dispatch core shared by **every** serving
surface: Model-ID resolution through both id maps, the fused MLP kernel
(``kernels.fixedpoint_mlp``), the tree-ensemble lane
(``kernels.forest_traversal`` — pointer-chase or range-table variant) and
per-model output masking, over already-parsed int32 feature codes.
``core.inference.DataPlaneEngine`` jits it directly for the feature path
(``run_features``) and composes it with the byte codec for the wire path
(``process``, the byte-level reference) — one definition, so the two
surfaces cannot drift.  Raw traffic enters here too: the flow update and
the feature-spec gather run on the host (``repro.flow``), and the wire
byte layout is paid once, at egress.

Everything here is trace-time composition: pure jnp/Pallas-kernel call
graphs with static lane/variant switches, jitted by their callers (the
engine owns the compiled programs and the trace counter).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .ops import forest_traverse, fused_mlp

__all__ = ["LaneConfig", "serve_lanes"]


class LaneConfig(NamedTuple):
    """Static (synthesis-time) configuration of the serving program: every
    field changes the compiled graph, none can change per batch."""

    frac: int
    sig_coeffs: tuple
    leaky_alpha_q: int
    max_features: int
    max_tree_depth: int
    backend: str = "auto"           # kernel backend selection
    kernel_variant: str = "int16"   # MLP weight lane
    forest_variant: str = "chase"   # forest traversal lowering


def serve_lanes(x0: jax.Array, model_id: jax.Array, tables, ftables, rtables,
                cfg: LaneConfig, *, use_mlp: bool,
                use_forest: bool) -> jax.Array:
    """The lane-dispatch core: parsed feature codes → output codes.

    x0 (B, W≥tables width) int32 codes at ``cfg.frac`` · model_id (B,) int32
    → (B, min(max_features, W)) int32 output codes.  Per packet, whichever
    id map resolves the Model ID picks the egress row; unresolved ids (and
    dead padding rows, which carry Model ID 0) egress zeros.
    """
    width = tables.w.shape[-1]
    if x0.shape[1] < width:
        x0 = jnp.pad(x0, ((0, 0), (0, width - x0.shape[1])))
    else:
        x0 = x0[:, :width]
    model_id = model_id.astype(jnp.int32)
    lane = jnp.arange(width)[None, :]

    if use_mlp:
        slot = tables.id_map[model_id]  # (B,) — mixed models
        valid = slot >= 0
        slot = jnp.maximum(slot, 0)
        x = fused_mlp(x0, slot, tables.w, tables.b, tables.act,
                      tables.layer_on, frac=cfg.frac,
                      sig_coeffs=cfg.sig_coeffs,
                      leaky_alpha_q=cfg.leaky_alpha_q,
                      backend=cfg.backend, variant=cfg.kernel_variant)
        out_dim = tables.out_dim[slot][:, None]
        outputs = jnp.where((lane < out_dim) & valid[:, None], x, 0)
    else:
        # lane-pure forest batch: ids not in the forest map (including
        # uninstalled ones) egress zeroed, same as MLP-lane invalid ids
        outputs = jnp.zeros_like(x0)

    if use_forest:
        fslot = ftables.id_map[model_id]
        fvalid = fslot >= 0
        fslot = jnp.maximum(fslot, 0)
        fx = forest_traverse(x0, fslot, ftables.nodes, ftables.tree_on,
                             ftables.mode, max_depth=cfg.max_tree_depth,
                             frac=cfg.frac, backend=cfg.backend,
                             variant=cfg.forest_variant, ranges=rtables)
        f_out_dim = ftables.out_dim[fslot][:, None]
        fout = jnp.where(lane < f_out_dim, fx, 0)
        outputs = jnp.where(fvalid[:, None], fout, outputs)

    return outputs[:, : cfg.max_features]
