"""A configuration's tenants, drawn from the seed, and the server that
serves them.

Tenants are drawn as integer tables (weight and bias codes, forest node
records), the form the data plane holds them in.  They are installed
through the public control-plane surface: MLP codes as floats on the
fixed-point grid, which the control plane's quantiser maps back to the
same codes, and forests as packed node tables.  The reference reads the
same integer tables, never the program's copy.
"""

from __future__ import annotations

import numpy as np

from .gen import stream_rng
from .reference import ACT_CODES, CLASSIFY


def _random_tree(rng, *, n_internal: int, depth: int, n_feat: int,
                 leaf_fn, thresh_exp) -> np.ndarray:
    """One tree as ``(nodes, 5)`` records (feature, threshold code, left,
    right, leaf payload), leaves self-looping: random splits of random
    leaves above ``depth`` until ``n_internal`` internal nodes exist."""
    nodes = [[0, 0, 0, 0, 0]]
    node_depth = [0]
    leaves = [0]
    internal = 0
    while internal < n_internal:
        open_ = [i for i in leaves if node_depth[i] < depth]
        if not open_:
            break
        i = open_[int(rng.integers(0, len(open_)))]
        leaves.remove(i)
        lo, hi = thresh_exp
        th = int(2.0 ** rng.uniform(lo, hi))
        left, right = len(nodes), len(nodes) + 1
        nodes[i] = [int(rng.integers(0, n_feat)), th, left, right, 0]
        for _ in range(2):
            nodes.append([0, 0, len(nodes), len(nodes), 0])
            node_depth.append(node_depth[i] + 1)
            leaves.append(len(nodes) - 1)
        internal += 1
    for i in leaves:
        nodes[i][4] = leaf_fn()
    return np.asarray(nodes, np.int32)


def make_tenants(cfg: dict, seed: int, feature_lanes) -> list:
    """The configuration's tenants from the seed.  ``feature_lanes`` (a
    raw mix's flow lanes, or ``None`` for encapsulated traffic) sets each
    tenant's FeatureSpec: the lanes tiled over its input columns in a
    seeded order, so every lane feeds every tenant."""
    srv, ten = cfg["server"], cfg["tenants"]
    frac, width = srv["frac_bits"], srv["max_width"]
    rng = stream_rng(seed, 1)
    out = []
    m = ten.get("mlp")
    if m:
        wmax = (1 << (srv["weight_bits"] - 1)) - 1
        for k, mid in enumerate(m["ids"]):
            dims = [width] * m["layers"] + [m["out_dims"][k]]
            w = [np.clip(np.round(rng.normal(size=(a, b)) * m["weight_std"]
                                  * (1 << frac)), -wmax, wmax).astype(np.int32)
                 for a, b in zip(dims[:-1], dims[1:])]
            b = [np.round(rng.normal(size=(d,)) * m["bias_std"]
                          * (1 << 2 * frac)).astype(np.int32)
                 for d in dims[1:]]
            acts = m["activations"][k % len(m["activations"])]
            spec = None
            if feature_lanes is not None:
                spec = tuple(int(c) for c in rng.permutation(
                    np.resize(np.asarray(feature_lanes), width)))
            out.append({"kind": "mlp", "id": int(mid), "w": w, "b": b,
                        "acts": [ACT_CODES[a] for a in acts],
                        "act_names": list(acts), "out_dim": dims[-1],
                        "spec": spec})
    f = ten.get("forest")
    if f:
        n_nodes = srv["max_nodes"]
        n_internal = (n_nodes - 1) // 2
        for k, mid in enumerate(f["ids"]):
            classify = f["tasks"][k % len(f["tasks"])] == "classify"
            n_cls = f["classes"]
            if classify:
                def leaf_fn():
                    return int(rng.integers(0, n_cls))
            else:
                step = (1 << frac) // f["trees"]

                def leaf_fn():
                    return int(rng.integers(0, 4 * step))
            n_feat = len(feature_lanes) if feature_lanes is not None \
                else width
            n_feat = min(n_feat, f["spec_columns"])
            trees = [_random_tree(rng, n_internal=n_internal,
                                  depth=srv["max_tree_depth"],
                                  n_feat=n_feat, leaf_fn=leaf_fn,
                                  thresh_exp=f["threshold_log2"])
                     for _ in range(f["trees"])]
            n = max(t.shape[0] for t in trees)
            nodes = np.zeros((f["trees"], n, 5), np.int32)
            for i, t in enumerate(trees):
                nodes[i, : t.shape[0]] = t
            spec = None
            if feature_lanes is not None:
                spec = tuple(int(c) for c in rng.permutation(
                    np.resize(np.asarray(feature_lanes), f["spec_columns"])))
            out.append({"kind": "forest", "id": int(mid), "nodes": nodes,
                        "tree_on": np.ones(f["trees"], np.int32),
                        "mode": CLASSIFY if classify else 0,
                        "out_dim": n_cls if classify else 1,
                        "spec": spec})
    return out


def _depth(nodes: np.ndarray) -> int:
    best = 0
    for tree in nodes:
        stack = [(0, 0)]
        while stack:
            i, d = stack.pop()
            left, right = int(tree[i, 2]), int(tree[i, 3])
            if left == i and right == i:
                best = max(best, d)
            else:
                stack += [(left, d + 1), (right, d + 1)]
    return best


def build_server(cfg: dict, overrides: dict | None = None):
    """A ``PacketServer`` (one shard) or ``ShardedPacketServer`` with the
    configuration's settings; ``overrides`` replaces some of them."""
    kw = dict(cfg["server"])
    kw.update(overrides or {})
    shards = cfg.get("shards", 1)
    if shards > 1:
        from repro.serve import ShardedPacketServer
        return ShardedPacketServer(n_shards=shards, **kw)
    from repro.launch.serve import PacketServer
    return PacketServer(**kw)


def install(srv, cfg: dict, tenants: list) -> None:
    """Install every tenant (and its FeatureSpec) on ``srv``."""
    from repro.forest import PackedForest
    frac = cfg["server"]["frac_bits"]
    for t in tenants:
        if t["kind"] == "mlp":
            layers = [(w.astype(np.float32) / (1 << frac),
                       b.astype(np.float32) / (1 << 2 * frac))
                      for w, b in zip(t["w"], t["b"])]
            names = t["act_names"]
            srv.install(t["id"], layers, names[:-1],
                        final_activation=names[-1])
        else:
            srv.install_forest(t["id"], PackedForest(
                nodes=t["nodes"], tree_on=t["tree_on"], mode=t["mode"],
                out_dim=t["out_dim"], depth=_depth(t["nodes"]),
                frac_bits=frac))
        if t["spec"] is not None:
            srv.install_feature_spec(t["id"], t["spec"])
