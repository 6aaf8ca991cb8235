"""Tentpole tests for the in-network tree-ensemble engine (PR 3):

  * pure-NumPy CART trainer + import path (``repro.forest.compile``)
  * compile→traverse round trip: the Pallas kernel and both jnp lowerings
    must be **bit-exact** against the pure-Python scalar oracle
    (``kernels.ref.forest_traverse_numpy``) on random trees and random
    packed rows — the same contract the MLP kernel carries
  * ``ForestTables`` generation-swap protocol in the control plane (zero
    retraces on install/remove, shared generation with the MLP family)
  * mixed MLP+forest dispatch through ``DataPlaneEngine`` and the full
    ingress pipeline / ``PacketServer`` serving surface
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp

from repro.core import packet as pk
from repro.core.control_plane import ControlPlane
from repro.core.inference import DataPlaneEngine
from repro.data.packets import anomaly_dataset, qos_dataset
from repro.forest import (FOREST_CLASSIFY, FOREST_REGRESS, Forest,
                          PackedForest, pack_forest, predict_float,
                          train_forest, train_tree)
from repro.kernels import ops, ref

FRAC = 8
WIDTH = 8


# ---------------------------------------------------------------------------
# shared generators
# ---------------------------------------------------------------------------


def _random_nodes(rng, n_trees, n_nodes, width, depth, mode, out_dim):
    """Random *valid* packed node tables for one forest: binary trees grown
    level-order within the depth bound, leaves self-looping."""
    nodes = np.zeros((n_trees, n_nodes, 5), np.int32)
    for t in range(n_trees):
        is_leaf = np.ones(n_nodes, bool)
        left = np.arange(n_nodes, dtype=np.int64)
        right = np.arange(n_nodes, dtype=np.int64)
        nxt, queue = 1, [(0, 0)]
        n_splits = int(rng.integers(0, n_nodes // 2 + 1))
        done = 0
        while queue and done < n_splits and nxt + 1 < n_nodes:
            i, d = queue.pop(0)
            if d >= depth:
                continue
            is_leaf[i] = False
            left[i], right[i] = nxt, nxt + 1
            queue += [(nxt, d + 1), (nxt + 1, d + 1)]
            nxt += 2
            done += 1
        internal = ~is_leaf
        nodes[t, internal, 0] = rng.integers(0, width, internal.sum())
        nodes[t, internal, 1] = rng.integers(-800, 800, internal.sum())
        nodes[t, :, 2] = left
        nodes[t, :, 3] = right
        if mode == FOREST_CLASSIFY:
            leaf_vals = rng.integers(0, out_dim, n_nodes)
        else:
            leaf_vals = rng.integers(-1500, 1500, n_nodes)
        nodes[t, is_leaf, 4] = leaf_vals[is_leaf]
    return nodes


def _random_forest_tables(rng, n_forests, width, depth):
    """Stacked (F, T, N, 5) tables + tree_on/mode for the kernel contract
    tests (mixed classify/regress forests, ragged tree counts)."""
    n_trees = int(rng.integers(1, 5))
    n_nodes = int(rng.integers(2, 17))
    nodes = np.zeros((n_forests, n_trees, n_nodes, 5), np.int32)
    tree_on = np.zeros((n_forests, n_trees), np.int32)
    mode = rng.integers(0, 2, n_forests).astype(np.int32)
    for f in range(n_forests):
        out_dim = int(rng.integers(2, width + 1))
        nodes[f] = _random_nodes(rng, n_trees, n_nodes, width, depth,
                                 int(mode[f]), out_dim)
        tree_on[f, : int(rng.integers(1, n_trees + 1))] = 1
    return nodes, tree_on, mode


def _install_mlp(cp, rng, model_id, scale=0.3):
    w1 = rng.normal(size=(WIDTH, WIDTH)).astype(np.float32) * scale
    w2 = rng.normal(size=(WIDTH, 2)).astype(np.float32) * scale
    cp.install(model_id, [(w1, np.zeros(WIDTH, np.float32)),
                          (w2, np.zeros(2, np.float32))],
               ["relu"], final_activation="sigmoid")


def _wire(rng, n, mids):
    mids = np.broadcast_to(np.asarray(mids, np.int32), (n,))
    codes = rng.integers(-2000, 2000, (n, WIDTH)).astype(np.int32)
    return np.asarray(pk.encode_packets(jnp.asarray(mids), jnp.int32(FRAC),
                                        jnp.asarray(codes))), codes


def _train_small(rng, task, **kw):
    if task == "classify":
        X, y = anomaly_dataset(rng, 400, WIDTH)
    else:
        X, y = qos_dataset(rng, 400, WIDTH)
    kw.setdefault("n_trees", 5)
    kw.setdefault("max_depth", 4)
    kw.setdefault("max_nodes", 31)
    return train_forest(X, y, task=task, seed=int(rng.integers(1 << 30)),
                        **kw), X, y


# ---------------------------------------------------------------------------
# trainer + compiler
# ---------------------------------------------------------------------------


class TestTrainer:
    def test_classifier_learns_planted_structure(self):
        rng = np.random.default_rng(0)
        X, y = anomaly_dataset(rng, 1500, WIDTH)
        f = train_forest(X[:1000], y[:1000], task="classify", n_trees=8,
                         max_depth=5, seed=1)
        acc = (predict_float(f, X[1000:]) == y[1000:]).mean()
        base = max(y[1000:].mean(), 1 - y[1000:].mean())  # majority class
        assert acc > base + 0.05
        assert acc > 0.9

    def test_regressor_beats_mean_predictor(self):
        rng = np.random.default_rng(1)
        X, y = qos_dataset(rng, 1500, WIDTH)
        f = train_forest(X[:1000], y[:1000], task="regress", n_trees=8,
                         max_depth=5, seed=2)
        pred = predict_float(f, X[1000:])
        mse = ((pred - y[1000:]) ** 2).mean()
        assert mse < 0.25 * y[1000:].var()

    def test_tree_respects_bounds(self):
        rng = np.random.default_rng(2)
        X, y = anomaly_dataset(rng, 600, WIDTH)
        t = train_tree(X, y, task="classify", max_depth=3, max_nodes=11)
        assert t.depth() <= 3
        assert t.n_nodes <= 11

    @pytest.mark.parametrize("max_nodes", [7, 16, 31, 64])
    def test_node_budget_is_hard_on_deep_trees(self, max_nodes):
        """Noisy labels grow every branch to full depth, so the budget binds
        while right siblings are still pending: the tree must stay within
        it (a control plane with the same max_nodes accepts it)."""
        rng = np.random.default_rng(max_nodes)
        X = rng.normal(size=(2000, WIDTH))
        y = rng.integers(0, 2, 2000)
        t = train_tree(X, y, task="classify", max_depth=8,
                       max_nodes=max_nodes, min_leaf=1)
        assert t.n_nodes <= max_nodes

    def test_import_path_round_trips(self):
        """from_arrays on a trained tree's own arrays predicts identically."""
        rng = np.random.default_rng(3)
        f, X, _ = _train_small(rng, "classify")
        imported = Forest.from_arrays(
            [t.feature for t in f.trees], [t.threshold for t in f.trees],
            [t.left for t in f.trees], [t.right for t in f.trees],
            [t.value for t in f.trees], task="classify",
            n_classes=f.n_classes)
        np.testing.assert_array_equal(predict_float(imported, X),
                                      predict_float(f, X))

    def test_pack_leaves_self_loop(self):
        rng = np.random.default_rng(4)
        f, _, _ = _train_small(rng, "regress")
        packed = pack_forest(f, frac_bits=FRAC)
        for ti, tree in enumerate(f.trees):
            leaves = np.nonzero(tree.left < 0)[0]
            np.testing.assert_array_equal(packed.nodes[ti, leaves, 2], leaves)
            np.testing.assert_array_equal(packed.nodes[ti, leaves, 3], leaves)
        assert packed.mode == FOREST_REGRESS
        assert packed.out_dim == 1
        assert packed.depth == max(t.depth() for t in f.trees)

    def test_quantized_classify_matches_float_majority(self):
        """The accuracy contract (not bit-level): argmax of the data plane's
        vote lanes reproduces the float majority vote on nearly all rows
        (disagreement only at quantization-boundary splits)."""
        rng = np.random.default_rng(5)
        f, X, _ = _train_small(rng, "classify", n_trees=7)
        packed = pack_forest(f, frac_bits=FRAC)
        xq = np.round(X * (1 << FRAC)).astype(np.int32)
        out = ref.forest_traverse_numpy(
            xq, np.zeros(len(xq), np.int32), packed.nodes[None],
            packed.tree_on[None], np.asarray([packed.mode], np.int32),
            max_depth=packed.depth, frac=FRAC)
        got = out[:, : f.n_classes].argmax(1)
        agree = (got == predict_float(f, X)).mean()
        assert agree > 0.97


# ---------------------------------------------------------------------------
# kernel contract: every lowering bit-exact vs the pure-Python oracle
# ---------------------------------------------------------------------------


class TestTraversalBitExact:
    def _check_all_backends(self, x, slot, nodes, tree_on, mode, depth):
        want = ref.forest_traverse_numpy(x, slot, nodes, tree_on, mode,
                                         max_depth=depth, frac=FRAC)
        for backend in ("auto", "ref", "pallas"):
            got = np.asarray(ops.forest_traverse(
                jnp.asarray(x), jnp.asarray(slot), jnp.asarray(nodes),
                jnp.asarray(tree_on), jnp.asarray(mode),
                max_depth=depth, frac=FRAC, backend=backend))
            np.testing.assert_array_equal(
                got, want, err_msg=f"backend={backend} diverged from the "
                                   "pure-Python oracle")

    @settings(max_examples=12, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=10 ** 6),
           n_forests=st.integers(min_value=1, max_value=4),
           depth=st.integers(min_value=1, max_value=4))
    def test_property_random_tables_all_backends(self, seed, n_forests,
                                                 depth):
        """Arbitrary valid node tables, arbitrary packed rows: pallas,
        masked-ref and gathered lowerings all reproduce the scalar oracle
        bit for bit."""
        rng = np.random.default_rng(seed)
        nodes, tree_on, mode = _random_forest_tables(rng, n_forests, WIDTH,
                                                     depth)
        n = int(rng.integers(1, 40))
        x = rng.integers(-1000, 1000, (n, WIDTH)).astype(np.int32)
        slot = rng.integers(0, n_forests, n).astype(np.int32)
        self._check_all_backends(x, slot, nodes, tree_on, mode, depth)

    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=10 ** 6),
           task=st.sampled_from(["classify", "regress"]))
    def test_property_trained_forest_round_trip(self, seed, task):
        """compile→traverse round trip on *trained* ensembles: pack a CART
        forest, run random wire rows through every lowering, compare to the
        oracle bit for bit."""
        rng = np.random.default_rng(seed)
        f, _, _ = _train_small(rng, task, n_trees=4)
        packed = pack_forest(f, frac_bits=FRAC)
        n = int(rng.integers(1, 32))
        x = rng.integers(-800, 800, (n, WIDTH)).astype(np.int32)
        slot = np.zeros(n, np.int32)
        self._check_all_backends(
            x, slot, packed.nodes[None], packed.tree_on[None],
            np.asarray([packed.mode], np.int32), max(packed.depth, 1))

    def test_padded_trees_contribute_nothing(self):
        rng = np.random.default_rng(7)
        nodes, tree_on, mode = _random_forest_tables(rng, 2, WIDTH, 3)
        x = rng.integers(-500, 500, (16, WIDTH)).astype(np.int32)
        slot = rng.integers(0, 2, 16).astype(np.int32)
        base = ref.forest_traverse_numpy(x, slot, nodes, tree_on, mode,
                                         max_depth=3, frac=FRAC)
        # garbage in dead trees' tables must not change anything
        noisy = nodes.copy()
        dead = tree_on == 0
        noisy[dead] = rng.integers(0, 2, noisy[dead].shape).astype(np.int32)
        noisy[dead, :, 2] = 0  # keep pointers in-range
        noisy[dead, :, 3] = 0
        got = ref.forest_traverse_numpy(x, slot, noisy, tree_on, mode,
                                        max_depth=3, frac=FRAC)
        np.testing.assert_array_equal(got, base)


# ---------------------------------------------------------------------------
# control plane: ForestTables generation-swap protocol
# ---------------------------------------------------------------------------


class TestForestControlPlane:
    def _cp(self, **kw):
        kw.setdefault("max_models", 4)
        kw.setdefault("max_width", WIDTH)
        kw.setdefault("frac_bits", FRAC)
        kw.setdefault("max_forests", 3)
        kw.setdefault("max_trees", 8)
        kw.setdefault("max_nodes", 32)
        kw.setdefault("max_tree_depth", 5)
        return ControlPlane(**kw)

    def test_install_bumps_generation_and_caches_snapshot(self):
        rng = np.random.default_rng(10)
        cp = self._cp()
        f, _, _ = _train_small(rng, "classify")
        v0 = cp.version
        cp.install_forest(5, f)
        assert cp.version == v0 + 1
        t1 = cp.forest_tables()
        assert cp.forest_tables() is t1  # cached per generation
        cp.install_forest(5, f)
        assert cp.forest_tables() is not t1  # new generation, new snapshot

    def test_remove_recycles_slots_and_unroutes(self):
        rng = np.random.default_rng(11)
        cp = self._cp()
        f, _, _ = _train_small(rng, "classify")
        s0 = cp.install_forest(5, f)
        cp.install_forest(6, f)
        cp.remove(5)
        assert int(np.asarray(cp.forest_tables().id_map)[5]) == -1
        assert cp.install_forest(7, f) == s0  # recycled
        cp.remove(404)  # unknown id: no-op, no error

    def test_forest_table_full(self):
        rng = np.random.default_rng(12)
        cp = self._cp(max_forests=1)
        f, _, _ = _train_small(rng, "classify")
        cp.install_forest(1, f)
        with pytest.raises(ValueError, match="forest table full"):
            cp.install_forest(2, f)

    def test_validation_rejects_out_of_bounds_forests(self):
        rng = np.random.default_rng(13)
        cp = self._cp(max_tree_depth=2)
        f, _, _ = _train_small(rng, "classify", max_depth=4)
        assert max(t.depth() for t in f.trees) > 2
        with pytest.raises(ValueError, match="unroll bound"):
            cp.install_forest(1, f)
        cp2 = self._cp(max_trees=2)
        with pytest.raises(ValueError, match="trees > max"):
            cp2.install_forest(1, f)
        # feature index beyond the data-plane width
        bad = PackedForest(
            nodes=np.asarray([[[WIDTH + 3, 0, 1, 2, 0],
                               [0, 0, 1, 1, 0],
                               [0, 0, 2, 2, 1]]], np.int32),
            tree_on=np.ones(1, np.int32), mode=FOREST_CLASSIFY,
            out_dim=2, depth=1, frac_bits=FRAC)
        with pytest.raises(ValueError, match="splits on feature"):
            self._cp().install_forest(1, bad)
        with pytest.raises(ValueError, match="fractional bits"):
            self._cp(frac_bits=5).install_forest(
                1, pack_forest(f, frac_bits=FRAC))
        # classification leaf label outside its vote lanes: would silently
        # vanish at egress (masked lane) and crash the scalar oracle
        bad_leaf = PackedForest(
            nodes=np.asarray([[[1, 0, 1, 2, 0],
                               [0, 0, 1, 1, 7],
                               [0, 0, 2, 2, 1]]], np.int32),
            tree_on=np.ones(1, np.int32), mode=FOREST_CLASSIFY,
            out_dim=2, depth=1, frac_bits=FRAC)
        with pytest.raises(ValueError, match="leaf label"):
            self._cp().install_forest(1, bad_leaf)

    def test_one_id_namespace_across_families(self):
        rng = np.random.default_rng(14)
        cp = self._cp()
        f, _, _ = _train_small(rng, "classify")
        _install_mlp(cp, rng, 9)
        with pytest.raises(ValueError, match="installed as an MLP"):
            cp.install_forest(9, f)
        cp.install_forest(3, f)
        with pytest.raises(ValueError, match="installed as a forest"):
            _install_mlp(cp, rng, 3)
        cp.remove(3)
        _install_mlp(cp, rng, 3)  # freed id is usable by the other family

    def test_forest_active_is_monotone(self):
        rng = np.random.default_rng(15)
        cp = self._cp()
        assert not cp.forest_active
        f, _, _ = _train_small(rng, "classify")
        cp.install_forest(1, f)
        assert cp.forest_active
        cp.remove(1)
        assert cp.forest_active  # latched: the engine's static lane switch


# ---------------------------------------------------------------------------
# engine: mixed-family dispatch + the zero-retrace acceptance property
# ---------------------------------------------------------------------------


class TestEngineDispatch:
    def _setup(self, rng):
        cp = ControlPlane(max_models=4, max_layers=2, max_width=WIDTH,
                          frac_bits=FRAC, max_forests=2, max_trees=8,
                          max_nodes=32, max_tree_depth=5)
        _install_mlp(cp, rng, 1)
        f, _, _ = _train_small(rng, "classify")
        cp.install_forest(2, f)
        fr, _, _ = _train_small(rng, "regress")
        cp.install_forest(3, fr)
        eng = DataPlaneEngine(cp, max_features=WIDTH)
        return cp, eng

    def test_mixed_batch_routes_per_packet(self):
        """One batch interleaving MLP, classify-forest, regress-forest and
        unknown IDs: every packet's egress equals its own family's lane,
        bit for bit."""
        rng = np.random.default_rng(20)
        cp, eng = self._setup(rng)
        mids = rng.choice([1, 2, 3, 60000], 96).astype(np.int32)
        pkts, codes = _wire(rng, 96, mids)
        out = np.asarray(eng.process(pkts))
        got = np.asarray(pk.parse_packets(jnp.asarray(out), WIDTH).features_q)

        ft = cp.forest_tables()
        fslot = np.asarray(ft.id_map)[mids]
        fwant = ref.forest_traverse_numpy(
            codes, np.maximum(fslot, 0), np.asarray(ft.nodes),
            np.asarray(ft.tree_on), np.asarray(ft.mode),
            max_depth=cp.max_tree_depth, frac=FRAC)
        out_dim = np.asarray(ft.out_dim)[np.maximum(fslot, 0)]
        for i in range(96):
            if mids[i] in (2, 3):
                d = int(out_dim[i])
                np.testing.assert_array_equal(got[i, :d], fwant[i, :d])
                assert not got[i, d:].any()  # lanes beyond out_dim zeroed
            elif mids[i] == 60000:
                assert not got[i].any()  # unknown id in either family
        # MLP packets equal a pure-MLP engine's output for the same bytes
        sel = mids == 1
        cp2 = ControlPlane(max_models=4, max_layers=2, max_width=WIDTH,
                           frac_bits=FRAC)
        _install_mlp(cp2, np.random.default_rng(20), 1)
        eng2 = DataPlaneEngine(cp2, max_features=WIDTH)
        want_mlp = np.asarray(eng2.process(pkts[sel]))
        np.testing.assert_array_equal(out[sel], want_mlp)

    def test_forest_reinstall_zero_retraces(self):
        """The acceptance criterion: hot-swapping a retrained forest during
        serving never recompiles the data plane."""
        rng = np.random.default_rng(21)
        cp, eng = self._setup(rng)
        pkts, _ = _wire(rng, 64, rng.choice([1, 2, 3], 64))
        eng.process(pkts)
        traces = eng.trace_count
        for seed in (1, 2):
            f2, _, _ = _train_small(np.random.default_rng(seed), "classify")
            cp.install_forest(2, f2)
            eng.process(pkts)
        cp.remove(3)  # forest remove mid-serving: also retrace-free
        eng.process(pkts)
        assert eng.trace_count == traces

    def test_reinstall_actually_changes_outputs(self):
        rng = np.random.default_rng(22)
        cp, eng = self._setup(rng)
        pkts, _ = _wire(rng, 64, 2)
        old = np.asarray(eng.process(pkts))
        f2, _, _ = _train_small(np.random.default_rng(99), "regress")
        cp.remove(2)
        cp.install_forest(2, f2)  # same id, different task entirely
        new = np.asarray(eng.process(pkts))
        assert not np.array_equal(old, new)

    def test_backend_ref_matches_auto_end_to_end(self):
        rng = np.random.default_rng(23)
        cp, eng = self._setup(rng)
        eng_ref = DataPlaneEngine(cp, max_features=WIDTH, backend="ref")
        pkts, _ = _wire(rng, 48, rng.choice([1, 2, 3], 48))
        np.testing.assert_array_equal(np.asarray(eng.process(pkts)),
                                      np.asarray(eng_ref.process(pkts)))


# ---------------------------------------------------------------------------
# serving integration: pipeline cache + PacketServer
# ---------------------------------------------------------------------------


class TestForestServing:
    def _server(self, rng, **kw):
        from repro.launch.serve import PacketServer
        srv = PacketServer(max_models=4, max_layers=2, max_width=WIDTH,
                           frac_bits=FRAC, max_forests=2, max_trees=8,
                           max_nodes=32, max_tree_depth=5, **kw)
        _install_mlp(srv.control_plane, rng, 1)
        f, _, _ = _train_small(rng, "classify")
        srv.install_forest(2, f)
        return srv

    def test_stream_results_match_sync_mixed_traffic(self):
        rng = np.random.default_rng(30)
        srv = self._server(rng, ingress_batch=32)
        chunks = [_wire(rng, n, rng.choice([1, 2], n))[0]
                  for n in (5, 40, 17)]
        for ch in chunks:
            srv.submit_packets(ch)
        got = srv.drain_packets()
        want = np.asarray(srv.process(np.concatenate(chunks)))
        np.testing.assert_array_equal(
            np.stack(got), want[:, : srv.ingress.out_bytes])

    def test_forest_install_invalidates_result_cache(self):
        """The generation key covers the forest family: resubmitting the
        same bytes after a forest hot-swap must serve the new forest's
        outputs, never a cached row."""
        rng = np.random.default_rng(31)
        srv = self._server(rng, ingress_batch=16)
        base, _ = _wire(rng, 16, 2)
        srv.submit_packets(base)
        old = np.stack(srv.drain_packets())
        f2, _, _ = _train_small(np.random.default_rng(77), "classify",
                                n_trees=3)
        srv.install_forest(2, f2)
        srv.submit_packets(base)
        new = np.stack(srv.drain_packets())
        want = np.asarray(srv.process(base))[:, : srv.ingress.out_bytes]
        np.testing.assert_array_equal(new, want)

    def test_forest_reinstall_mid_serving_zero_retraces(self):
        """Hot-swapping retrained forests between windows of mixed
        MLP+forest stream traffic recompiles no lane program, and each
        window serves the forest installed before it."""
        rng = np.random.default_rng(36)
        srv = self._server(rng, ingress_batch=16, max_inflight=2)
        wire, _ = _wire(rng, 64, rng.choice([1, 2], 64))
        srv.submit_packets(wire)
        srv.drain_packets()
        np.asarray(srv.process(wire))    # the reference's own program
        traces = srv.engine.trace_count
        for seed in (1, 2):
            f2, _, _ = _train_small(np.random.default_rng(seed), "classify")
            srv.install_forest(2, f2)
            srv.submit_packets(wire)
            got = np.stack(srv.drain_packets())
            want = np.asarray(srv.process(wire))[:, : srv.ingress.out_bytes]
            np.testing.assert_array_equal(got, want)
        assert srv.engine.trace_count == traces

    def test_remove_forest_drops_cached_rows(self):
        rng = np.random.default_rng(32)
        srv = self._server(rng)
        base, _ = _wire(rng, 8, 2)
        srv.submit_packets(base)
        srv.drain_packets()
        assert srv.ingress.cache.contains_model(2)
        srv.remove(2)
        assert not srv.ingress.cache.contains_model(2)
        srv.submit_packets(base)
        got = np.stack(srv.drain_packets())
        want = np.asarray(srv.process(base))[:, : srv.ingress.out_bytes]
        np.testing.assert_array_equal(got, want)  # zeroed egress, not stale

    def test_mixed_traffic_dispatches_lane_pure_batches(self):
        """Family-aware staging: mixed MLP+forest traffic produces MLP-lane
        and forest-lane device batches (never paying both lanes per packet),
        and per-packet tickets keep submission order through the
        out-of-order family retirement."""
        rng = np.random.default_rng(33)
        srv = self._server(rng, ingress_batch=16, max_inflight=2)
        mids = rng.choice([1, 2], 200)
        wire, _ = _wire(rng, 200, mids)
        srv.submit_packets(wire)
        got = srv.drain_packets()
        lanes = srv.ingress.stats["lane_batches"]
        assert lanes["mlp"] > 0 and lanes["forest"] > 0
        assert lanes["both"] == 0  # no install raced the staging
        want = np.asarray(srv.process(wire))[:, : srv.ingress.out_bytes]
        np.testing.assert_array_equal(np.stack(got), want)

    def test_lane_dispatch_steady_state_zero_retraces(self):
        rng = np.random.default_rng(34)
        srv = self._server(rng, ingress_batch=16)
        wire, _ = _wire(rng, 64, rng.choice([1, 2], 64))
        srv.submit_packets(wire)
        srv.drain_packets()
        traces = srv.engine.trace_count
        for _ in range(3):  # steady mixed serving: both lane variants warm
            w2, _ = _wire(rng, 48, rng.choice([1, 2], 48))
            srv.submit_packets(w2)
            srv.drain_packets()
        assert srv.engine.trace_count == traces

    def test_install_racing_staging_falls_back_to_both_lanes(self):
        """An install between staging and dispatch may have reassigned an
        id's family — the batch must ride the always-correct both-lane
        program and still deliver the new generation's outputs."""
        rng = np.random.default_rng(35)
        srv = self._server(rng, ingress_batch=64, max_inflight=2)
        wire, _ = _wire(rng, 24, rng.choice([1, 2], 24))
        np.asarray(srv.process(wire))  # warm the both-lane variant
        srv.submit_packets(wire)       # staged, not yet dispatched
        f2, _, _ = _train_small(np.random.default_rng(88), "classify",
                                n_trees=3)
        srv.install_forest(2, f2)      # generation bump while staged
        got = srv.drain_packets()
        assert srv.ingress.stats["lane_batches"]["both"] > 0
        want = np.asarray(srv.process(wire))[:, : srv.ingress.out_bytes]
        np.testing.assert_array_equal(np.stack(got), want)

    def test_install_racing_run_snapshot_redispatches_both_lanes(self):
        """The narrow race inside _dispatch: a table write landing between
        the lane decision and run()'s snapshot must trigger a both-lane
        redispatch — a lane-pure program over the new tables could zero out
        packets whose id changed family."""
        rng = np.random.default_rng(36)
        srv = self._server(rng, ingress_batch=8, max_inflight=2)
        wire, _ = _wire(rng, 8, 2)  # one exact forest-lane batch
        np.asarray(srv.process(wire))  # warm the both-lane variant
        pipe, eng = srv.ingress, srv.engine
        f2, _, _ = _train_small(np.random.default_rng(5), "classify",
                                n_trees=3)
        real_run = eng.run_features
        fired = {"n": 0}

        def racing_run(x0, mids, **kw):
            # the writer lands after the pipeline sampled cp.version for
            # its lane decision but before the run snapshots the tables
            if fired["n"] == 0 and kw.get("lanes") == "forest":
                fired["n"] += 1
                srv.install_forest(2, f2)
            return real_run(x0, mids, **kw)

        eng.run_features = racing_run
        try:
            srv.submit_packets(wire)  # fills + dispatches the forest batch
            got = srv.drain_packets()
        finally:
            eng.run_features = real_run
        assert fired["n"] == 1
        assert pipe.stats["lane_batches"]["both"] >= 1  # redispatched
        want = np.asarray(srv.process(wire))[:, : pipe.out_bytes]
        np.testing.assert_array_equal(np.stack(got), want)

    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=10 ** 6),
           n=st.integers(min_value=1, max_value=48))
    def test_property_generation_invalidation_covers_forests(self, seed, n):
        """For arbitrary mixed traffic, a forest install between windows
        must flip every affected packet to the new generation's outputs —
        the pipeline/cache acceptance property extended to ForestTables."""
        rng = np.random.default_rng(seed)
        srv = self._server(rng, ingress_batch=16)
        base, _ = _wire(rng, n, rng.choice([1, 2], n))
        srv.submit_packets(base)
        srv.drain_packets()
        f2, _, _ = _train_small(np.random.default_rng(seed + 1), "classify",
                                n_trees=3)
        srv.install_forest(2, f2)
        srv.submit_packets(base)
        got = np.stack(srv.drain_packets())
        want = np.asarray(srv.process(base))[:, : srv.ingress.out_bytes]
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# range-table variant (PR 5): the pForest ternary-match lowering
# ---------------------------------------------------------------------------


def _ranges_of(nodes, tree_on, depth):
    """Compile per-forest range tables and pad to common static extents —
    the same layout ControlPlane.range_tables() publishes."""
    from repro.forest.ranges import pack_forest_ranges
    packs = [pack_forest_ranges(nodes[f], tree_on[f], max_depth=depth)
             for f in range(nodes.shape[0])]
    ni = max(p.feat.shape[1] for p in packs)
    nl = max(p.payload.shape[1] for p in packs)
    n_forests, n_trees = nodes.shape[0], nodes.shape[1]
    feat = np.zeros((n_forests, n_trees, ni), np.int32)
    th = np.full((n_forests, n_trees, ni), np.iinfo(np.int32).max, np.int32)
    lm = np.zeros((n_forests, n_trees, ni), np.uint32)
    pay = np.zeros((n_forests, n_trees, nl), np.int32)
    for f, p in enumerate(packs):
        feat[f, :, : p.feat.shape[1]] = p.feat
        th[f, :, : p.thresh.shape[1]] = p.thresh
        lm[f, :, : p.lmask.shape[1]] = p.lmask
        pay[f, :, : p.payload.shape[1]] = p.payload
    return feat, th, lm, pay


class TestRangeVariant:
    """The range-table forest lane must be bit-exact against the *same*
    scalar oracle as the pointer chase, on every backend — the three-way
    contract (range vs chase vs ``ref.forest_traverse_numpy``)."""

    def _check_three_way(self, x, slot, nodes, tree_on, mode, depth):
        want = ref.forest_traverse_numpy(x, slot, nodes, tree_on, mode,
                                         max_depth=depth, frac=FRAC)
        ranges = _ranges_of(nodes, tree_on, depth)
        xj = jnp.asarray(x)
        sj = jnp.asarray(slot)
        nj = jnp.asarray(nodes)
        tj = jnp.asarray(tree_on)
        mj = jnp.asarray(mode)
        chase = np.asarray(ops.forest_traverse(
            xj, sj, nj, tj, mj, max_depth=depth, frac=FRAC, backend="auto",
            variant="chase"))
        np.testing.assert_array_equal(chase, want)
        for backend in ("auto", "ref", "pallas"):
            got = np.asarray(ops.forest_traverse(
                xj, sj, nj, tj, mj, max_depth=depth, frac=FRAC,
                backend=backend, variant="range", ranges=ranges))
            np.testing.assert_array_equal(got, want)

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=10 ** 6),
           n_forests=st.integers(min_value=1, max_value=4),
           depth=st.integers(min_value=1, max_value=5))
    def test_property_three_way_random_tables(self, seed, n_forests, depth):
        """Arbitrary well-formed node tables, arbitrary packed rows: the
        range compilation reproduces both the chase and the scalar oracle
        bit for bit on every backend."""
        rng = np.random.default_rng(seed)
        nodes, tree_on, mode = _random_forest_tables(rng, n_forests, WIDTH,
                                                     depth)
        n = int(rng.integers(1, 40))
        x = rng.integers(-1000, 1000, (n, WIDTH)).astype(np.int32)
        slot = rng.integers(0, n_forests, n).astype(np.int32)
        self._check_three_way(x, slot, nodes, tree_on, mode, depth)

    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=10 ** 6),
           task=st.sampled_from(["classify", "regress"]))
    def test_property_three_way_trained_forests(self, seed, task):
        rng = np.random.default_rng(seed)
        f, _, _ = _train_small(rng, task, n_trees=4)
        packed = pack_forest(f, frac_bits=FRAC)
        n = int(rng.integers(1, 32))
        x = rng.integers(-800, 800, (n, WIDTH)).astype(np.int32)
        slot = np.zeros(n, np.int32)
        self._check_three_way(x, slot, packed.nodes[None],
                              packed.tree_on[None],
                              np.asarray([packed.mode], np.int32),
                              max(packed.depth, 1))

    def test_saturating_thresholds(self):
        """INT32_MAX thresholds (comparison always holds → always left) and
        INT32_MIN (holds only at exactly INT32_MIN) must agree between the
        chase and the range masks — the padding-entry convention must not
        blur with real saturated entries."""
        rng = np.random.default_rng(7)
        nodes, tree_on, mode = _random_forest_tables(rng, 2, WIDTH, 3)
        lo, hi = np.iinfo(np.int32).min, np.iinfo(np.int32).max
        for f in range(nodes.shape[0]):
            for t in range(nodes.shape[1]):
                internal = nodes[f, t, :, 2] != np.arange(nodes.shape[2])
                idx = np.nonzero(internal)[0]
                for k, i in enumerate(idx):
                    nodes[f, t, i, 1] = hi if k % 2 == 0 else lo
        x = np.concatenate([
            rng.integers(-1000, 1000, (20, WIDTH)).astype(np.int32),
            np.full((2, WIDTH), lo, np.int32),
            np.full((2, WIDTH), hi, np.int32)])
        slot = rng.integers(0, 2, x.shape[0]).astype(np.int32)
        self._check_three_way(x, slot, nodes, tree_on, mode, 3)

    def test_depth_one_stumps(self):
        """Depth-1 stumps: one range entry per tree, two leaves."""
        rng = np.random.default_rng(8)
        n_trees = 3
        nodes = np.zeros((1, n_trees, 3, 5), np.int32)
        for t in range(n_trees):
            nodes[0, t, 0] = (int(rng.integers(0, WIDTH)),
                              int(rng.integers(-500, 500)), 1, 2, 0)
            nodes[0, t, 1] = (0, 0, 1, 1, int(rng.integers(-900, 900)))
            nodes[0, t, 2] = (0, 0, 2, 2, int(rng.integers(-900, 900)))
        tree_on = np.ones((1, n_trees), np.int32)
        mode = np.asarray([FOREST_REGRESS], np.int32)
        x = rng.integers(-1000, 1000, (30, WIDTH)).astype(np.int32)
        slot = np.zeros(30, np.int32)
        self._check_three_way(x, slot, nodes, tree_on, mode, 1)

    def test_malformed_tree_rejected_at_install(self):
        """The range compiler's structural walk rejects a cyclic 'tree' the
        dense-table bounds checks cannot see."""
        from repro.forest import PackedForest
        cp = ControlPlane(max_models=2, max_width=WIDTH, max_forests=2,
                          max_trees=2, max_nodes=7, max_tree_depth=3)
        assert cp.range_available
        nodes = np.zeros((3, 5), np.int32)
        nodes[0] = (0, 10, 1, 2, 0)
        nodes[1] = (1, 20, 0, 2, 0)   # cycles back to the root
        nodes[2] = (0, 0, 2, 2, 5)
        bad = PackedForest(nodes=nodes[None], tree_on=np.ones(1, np.int32),
                           mode=FOREST_REGRESS, out_dim=1, depth=2,
                           frac_bits=FRAC)
        with pytest.raises(ValueError, match="tree"):
            cp.install_forest(9, bad)

    def test_engine_range_variant_end_to_end(self):
        """A range-variant engine serves the identical egress bytes as the
        chase engine on mixed MLP+forest traffic, and forest hot-swaps stay
        retrace-free (RangeTables ride the same generation swap)."""
        rng = np.random.default_rng(9)

        def build(variant):
            cp = ControlPlane(max_models=8, max_layers=2, max_width=WIDTH,
                              frac_bits=FRAC, max_forests=2, max_trees=4,
                              max_nodes=31, max_tree_depth=4)
            _install_mlp(cp, np.random.default_rng(5), 1)
            f, _, _ = _train_small(np.random.default_rng(6), "classify",
                                   n_trees=3)
            cp.install_forest(2, f)
            return cp, DataPlaneEngine(cp, max_features=WIDTH,
                                       forest_variant=variant)

        cp_c, eng_c = build("chase")
        cp_r, eng_r = build("range")
        wire, _ = _wire(rng, 64, rng.choice([1, 2], 64))
        want = np.asarray(eng_c.process(wire))
        got = np.asarray(eng_r.process(wire))
        np.testing.assert_array_equal(got, want)
        traces = eng_r.trace_count
        f2, _, _ = _train_small(np.random.default_rng(7), "classify",
                                n_trees=3)
        cp_r.install_forest(2, f2)
        got2 = np.asarray(eng_r.process(wire))
        assert eng_r.trace_count == traces  # hot-swap: zero retraces
        cp_c.install_forest(2, f2)
        np.testing.assert_array_equal(got2, np.asarray(eng_c.process(wire)))
