"""Needed-work functions against hand counts (CPU)."""

from __future__ import annotations

import types

import numpy as np
import pytest

from benchlib import work


def _mlp(dims):
    return {"kind": "mlp",
            "w": [np.zeros((a, b), np.int32) for a, b in zip(dims, dims[1:])],
            "b": [np.zeros(b, np.int32) for b in dims[1:]]}


def _forest(n_trees, on=None):
    tree_on = np.ones(n_trees, np.int32) if on is None else np.asarray(on)
    return {"kind": "forest", "nodes": np.zeros((n_trees, 63, 5), np.int32),
            "tree_on": tree_on}


def test_mlp_ops_count_the_tenant_own_layers():
    # 32x32 three times, then 32x5: 2 * (3 * 1024 + 160)
    assert work.tenant_ops(_mlp([32, 32, 32, 32, 5]), 6) == 6464


def test_forest_ops_one_compare_per_level_per_live_tree():
    assert work.tenant_ops(_forest(16), 6) == 96
    assert work.tenant_ops(_forest(4, on=[1, 0, 1, 1]), 6) == 18


def test_table_bytes_by_lane():
    tenants = [_mlp([32, 32, 4]), _forest(2)]
    # int16 weights: (1024 + 128) * 2 bytes, int32 biases: (32 + 4) * 4
    assert work.table_bytes(tenants, "mlp", 16) == 2304 + 144
    assert work.table_bytes(tenants, "forest", 16) == 2 * 63 * 5 * 4
    assert work.row_bytes(32) == 256


def test_roofline_share_takes_the_binding_bound():
    pk = work.peaks("TPU v5 lite")
    # compute-bound: 393e9 ops take 1 ms at peak; measured 4 ms -> 25%
    assert work.roofline_share(393e9, 0.0, 4e-3, pk) == pytest.approx(25.0)
    # memory-bound: 819e6 bytes take 1 ms; measured 2 ms -> 50%
    assert work.roofline_share(1.0, 819e6, 2e-3, pk) == pytest.approx(50.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


def test_kernel_roofline_reads_only_its_kernel_events():
    trace = {"op_seconds": {"fixedpoint_mlp_pallas": 2e-3,
                            "forest_range_pallas": 5e-3, "fusion": 1.0},
             "op_counts": {"fixedpoint_mlp_pallas": 4,
                           "forest_range_pallas": 4, "fusion": 8}}
    ctx = types.SimpleNamespace(
        trace=trace, peaks=work.peaks("TPU v5 lite"),
        work={"mlp": {"rows": 8192, "ops": 8192 * 6464,
                      "row_bytes": 8192 * 256, "table_bytes": 1000},
              "forest": {"rows": 0, "ops": 0, "row_bytes": 0,
                         "table_bytes": 1000}})
    nbytes = 8192 * 256 + 4 * 1000
    least = max(8192 * 6464 / 393e12, nbytes / 819e9)
    assert work.kernel_roofline(ctx, "mlp") == pytest.approx(
        100 * least / 2e-3)
    assert work.kernel_roofline(ctx, "forest") is None  # no rows computed
    ctx.trace = None
    assert work.kernel_roofline(ctx, "mlp") is None
