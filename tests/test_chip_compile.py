"""The serving kernels, and the serving program around them, compile for a
TPU v5e at the server's default shapes.

No chip is needed: the TPU compiler describes a v5e topology and compiles
for it ahead of time, so Mosaic's refusals (unsupported dot operand types,
selects with more than two arms, unaligned slices, VMEM overruns) surface
here and not on the chip.  Each test asserts the compiled program holds
the Pallas kernel (``tpu_custom_call``), i.e. that the kernel itself — not
a jnp stand-in — was lowered.

Shapes are ``PacketServer``'s defaults: a 2048-row batch of W=32 feature
lanes against M=16 models of L=4 layers, and F=8 forests of T=16 trees
with N=64 nodes (the range lowering's extents follow from N).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and a test session with
several workers imports this file in each of them.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.control_plane import ControlPlane
from repro.core.taylor import scaled_constants
from repro.forest.ranges import range_bounds
from repro.kernels import ops
from repro.kernels.fixedpoint_mlp import fixedpoint_mlp_pallas
from repro.kernels.forest_traversal import (forest_range_pallas,
                                            forest_traverse_pallas)
from repro.kernels.fused_serve import LaneConfig, serve_lanes

B, W, M, L = 2048, 32, 16, 4
F, T, N, DEPTH = 8, 16, 64, 6
FRAC = 8
SIG = (128, 64, 0, -1)


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel_compiles(fn, args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("variant,w_dtype", [("int16", jnp.int16),
                                             ("int8", jnp.int8)])
def test_fixedpoint_mlp_compiles_for_v5e(one_chip, variant, w_dtype):
    args = [_shape(one_chip, s, d) for s, d in (
        ((B, W), jnp.int32), ((B, 1), jnp.int32),
        ((L, M * W, W), w_dtype), ((L, M, W), jnp.int32),
        ((L, M, 1), jnp.int32), ((L, M, 1), jnp.int32))]
    _assert_kernel_compiles(
        lambda *a: fixedpoint_mlp_pallas(*a, frac=FRAC, sig_coeffs=SIG,
                                         leaky_alpha_q=3, variant=variant),
        args)


def test_forest_chase_compiles_for_v5e(one_chip):
    args = [_shape(one_chip, s, jnp.int32) for s in (
        (B, W), (B, 1), (T, F, 5 * N), (T, F, 1), (F, 1))]
    _assert_kernel_compiles(
        lambda *a: forest_traverse_pallas(*a, max_depth=DEPTH, frac=FRAC),
        args)


def test_forest_range_compiles_for_v5e(one_chip):
    ni, nl = range_bounds(N)
    args = [_shape(one_chip, s, jnp.int32) for s in (
        (B, W), (B, 1), (T, F, 3 * ni + nl), (T, F, 1), (F, 1))]
    _assert_kernel_compiles(
        lambda *a: forest_range_pallas(*a, n_entries=ni, n_leaves=nl,
                                       frac=FRAC),
        args)


@pytest.mark.parametrize("lanes", [(True, False), (False, True),
                                   (True, True)],
                         ids=["mlp", "forest", "both"])
def test_serving_program_compiles_for_v5e(one_chip, monkeypatch, lanes):
    """The whole feature-path program (``serve_lanes``: id maps, layout
    prep, both kernels, output masking) for a default ``PacketServer``.
    ``ops`` asks the process's backend which lowering to take; here it is
    told it runs on a TPU, so it takes the kernels with ``interpret=False``."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    use_mlp, use_forest = lanes
    cp = ControlPlane()
    ftables, rtables = cp.forest_snapshots(True)

    def shapes(tree):
        return jax.tree_util.tree_map(
            lambda a: _shape(one_chip, np.shape(a), np.asarray(a).dtype),
            tree)

    cfg = LaneConfig(frac=FRAC, sig_coeffs=tuple(
        int(c) for c in scaled_constants("sigmoid", 3, FRAC)),
        leaky_alpha_q=3, max_features=W, max_tree_depth=DEPTH,
        forest_variant="range")
    _assert_kernel_compiles(
        lambda x, m, t, f, r: serve_lanes(x, m, t, f, r, cfg,
                                          use_mlp=use_mlp,
                                          use_forest=use_forest),
        [_shape(one_chip, (B, W), jnp.int32),
         _shape(one_chip, (B,), jnp.int32), shapes(cp.tables()),
         shapes(ftables) if use_forest else None,
         shapes(rtables) if use_forest else None])
