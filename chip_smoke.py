#!/usr/bin/env python3
"""Smoke run of the raw-packet serving path on one TPU chip.

    python chip_smoke.py [--seed N]       # one chip: the serving path
    python chip_smoke.py --chips 4        # four chips: the sharded fabric

One chip: a ``PacketServer`` at its default widths (16 model slots of 4
layers × 32 lanes, 8 forest slots of 16 trees × 64 nodes, 2048-row ingress
batches) serves 8 MLP tenants and 8 random-forest tenants, each with its
own ``FeatureSpec``.  A seeded raw 5-tuple trace of 131072 packets over
16384 concurrent flows goes through ``submit_raw`` in chunks, then one
window of encapsulated feature packets through ``submit_packets``.  The
served egress must equal, byte for byte, a reference built on the host from
the plain oracles in ``repro.kernels.ref`` (the per-packet flow-register
walk, the gathered MLP on the CPU device, the scalar tree walk) — never
through the serving engine.  The run also requires zero error slots, zero
dispatch retries or failures, zero retraces after warm-up, and a Pallas
kernel (``tpu_custom_call``) in every serving program that ran.

``--chips 4``: the same deployment and trace on a 4-shard
``ShardedPacketServer`` whose shards sit on four distinct chips, compared
with the one-shard fabric and the oracle, then served again with one shard
killed mid-trace.

Everything is generated from ``--seed``.  The script exits non-zero, and
prints no result, when JAX finds no TPU or any check fails.  Its last line
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# -- the deployment ----------------------------------------------------------
FRAC = 8                 # PacketServer default frac_bits
TAYLOR_ORDER = 3         # PacketServer default taylor_order
LEAKY_ALPHA = 0.01       # DataPlaneEngine default leaky_alpha
WIDTH = 32               # PacketServer default max_width
MLP_IDS = tuple(range(1, 9))
FOREST_IDS = tuple(range(101, 109))
N_TREES, TREE_DEPTH, TREE_NODES = 16, 6, 64
# -- the traffic -------------------------------------------------------------
N_PACKETS = 131072
N_FLOWS = 16384
FLOW_CAPACITY_POW2 = 15
CHUNK = 8192
N_WIRE = 16384           # the submit_packets window
KILL_SHARD = 2


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# Deployment and traffic, all from the seed
# ---------------------------------------------------------------------------


def make_trace(seed: int) -> np.ndarray:
    from repro.data.packets import raw_trace
    return raw_trace(np.random.default_rng(seed), N_PACKETS, n_flows=N_FLOWS,
                     model_ids=MLP_IDS + FOREST_IDS, pattern="mixed")


def make_tenants(seed: int, flow_feats: np.ndarray, mids: np.ndarray):
    """8 MLPs (4 layers at full width, every activation) and 8 forests
    trained on the trace's own flow features, each with a FeatureSpec."""
    from repro.forest import train_forest
    rng = np.random.default_rng(seed + 1)
    acts = ["relu", "sigmoid", "leaky_relu", "hard_sigmoid"]
    tenants = []
    for k, mid in enumerate(MLP_IDS):
        dims = [WIDTH] * 4 + [2 + k]
        layers = [(rng.normal(size=(a, b)).astype(np.float32) * 0.2,
                   rng.normal(size=(b,)).astype(np.float32) * 0.2)
                  for a, b in zip(dims[:-1], dims[1:])]
        hidden = [acts[(k + i) % 4] for i in range(3)]
        spec = tuple(int(c) for c in rng.integers(0, 8, WIDTH))
        tenants.append(("mlp", mid, (layers, hidden, acts[(k + 3) % 4]),
                        spec))
    for k, mid in enumerate(FOREST_IDS):
        spec = tuple(int(c) for c in rng.permutation(8))
        rows = np.nonzero(mids == mid)[0][:1024]
        X = flow_feats[rows][:, list(spec)].astype(np.float64) / (1 << FRAC)
        task = "classify" if k % 2 == 0 else "regress"
        # target: the rank of a random projection (both classes always
        # occur, however many feature columns are constant)
        score = np.log1p(np.abs(X)) @ rng.normal(size=X.shape[1])
        y = np.argsort(np.argsort(score, kind="stable")) / len(rows)
        if task == "classify":
            y = (y >= 0.5).astype(np.int64)
        forest = train_forest(X, y, task=task, n_trees=N_TREES,
                              max_depth=TREE_DEPTH, max_nodes=TREE_NODES,
                              seed=seed + 10 + k)
        tenants.append(("forest", mid, forest, spec))
    return tenants


def make_deployment(seed: int):
    """The trace, its oracle flow features (``flow_update_numpy`` over an
    unbounded flow table) and the tenants trained on them."""
    from repro.data.packets import parse_raw_headers
    from repro.flow import FlowParams, reference_features
    raw = make_trace(seed)
    flow_feats = reference_features(raw, FlowParams(frac=FRAC))
    tenants = make_tenants(seed, flow_feats,
                           parse_raw_headers(raw).model_id)
    return raw, flow_feats, tenants


def install(srv, tenants) -> None:
    for family, mid, model, spec in tenants:
        if family == "mlp":
            layers, hidden, final = model
            srv.install(mid, layers, hidden, final_activation=final)
        else:
            srv.install_forest(mid, model)
        srv.install_feature_spec(mid, spec)


def make_wire(seed: int):
    """The submit_packets window: encapsulated feature packets for every
    tenant plus ids nobody installed (those egress zeroed)."""
    from repro.core.packet import encode_packets_np
    rng = np.random.default_rng(seed + 2)
    ids = np.asarray(MLP_IDS + FOREST_IDS + (999,), np.int32)
    mid = ids[rng.integers(0, ids.size, N_WIRE)]
    x = rng.integers(-(1 << 20), 1 << 20, (N_WIRE, WIDTH)).astype(np.int32)
    return mid, x, encode_packets_np(mid, FRAC, x)


# ---------------------------------------------------------------------------
# The oracle: plain kernels.ref reference semantics, on the host
# ---------------------------------------------------------------------------


class Reference:
    """Expected egress rows, computed from the installed tables with the
    reference oracles — never through the serving engine."""

    def __init__(self, cp):
        import jax
        from repro.core.taylor import scaled_constants
        self.t = jax.tree_util.tree_map(np.asarray, cp.tables())
        self.f = jax.tree_util.tree_map(
            np.asarray, cp.forest_snapshots(False)[0])
        self.max_depth = cp.max_tree_depth
        self.sig = tuple(int(c) for c in
                         scaled_constants("sigmoid", TAYLOR_ORDER, FRAC))
        self.alpha_q = int(round(LEAKY_ALPHA * (1 << FRAC)))
        self.cpu = jax.devices("cpu")[0]

    def outputs(self, x: np.ndarray, mid: np.ndarray) -> np.ndarray:
        import jax
        from repro.kernels.ref import (forest_traverse_numpy,
                                       fused_mlp_gather_ref)
        t, f = self.t, self.f
        lane = np.arange(WIDTH)[None, :]
        out = np.zeros((x.shape[0], WIDTH), np.int32)
        slot = t.id_map[mid]
        sel = np.nonzero(slot >= 0)[0]
        mlp = jax.jit(lambda *a: fused_mlp_gather_ref(
            *a, frac=FRAC, sig_coeffs=self.sig, leaky_alpha_q=self.alpha_q))
        with jax.default_device(self.cpu):
            for i in range(0, sel.size, 4096):
                rows = sel[i: i + 4096]
                y = np.asarray(mlp(x[rows], slot[rows], t.w, t.b, t.act,
                                   t.layer_on))
                out[rows] = np.where(lane < t.out_dim[slot[rows]][:, None],
                                     y, 0)
        fslot = f.id_map[mid]
        fsel = np.nonzero(fslot >= 0)[0]
        y = forest_traverse_numpy(x[fsel], fslot[fsel], f.nodes, f.tree_on,
                                  f.mode, max_depth=self.max_depth,
                                  frac=FRAC)
        out[fsel] = np.where(lane < f.out_dim[fslot[fsel]][:, None], y, 0)
        return out

    def egress(self, x: np.ndarray, mid: np.ndarray) -> np.ndarray:
        import jax
        import jax.numpy as jnp
        from repro.core.packet import ParsedBatch, emit_results
        out = self.outputs(x, mid)
        n = mid.shape[0]
        z = jnp.zeros((n,), jnp.int32)
        with jax.default_device(self.cpu):
            parsed = ParsedBatch(model_id=jnp.asarray(mid), feature_cnt=z,
                                 output_cnt=z, scale=z, flags=z,
                                 features_q=jnp.asarray(x))
            return np.asarray(emit_results(parsed, jnp.asarray(out), FRAC))


def raw_inputs(raw: np.ndarray, flow_feats: np.ndarray, tenants):
    """Model inputs of the raw trace: each packet's flow features landed on
    its tenant's FeatureSpec columns (unused columns read zero)."""
    from repro.data.packets import parse_raw_headers
    mid = parse_raw_headers(raw).model_id
    x = np.zeros((mid.shape[0], WIDTH), np.int32)
    for _, m, _, spec in tenants:
        rows = mid == m
        x[np.ix_(rows, np.arange(len(spec)))] = flow_feats[rows][:, spec]
    return mid, x


# ---------------------------------------------------------------------------
# Serving and checks
# ---------------------------------------------------------------------------


def serve_raw(srv, raw: np.ndarray, *, kill_at=None):
    for i in range(0, raw.shape[0], CHUNK):
        if i == kill_at and not srv.kill_shard(KILL_SHARD, "smoke drill"):
            raise AssertionError(f"shard {KILL_SHARD} refused to die")
        srv.submit_raw(raw[i: i + CHUNK])
    return srv.drain_packets()


def stacked(results, what: str) -> np.ndarray:
    errors = [r for r in results if not isinstance(r, np.ndarray)]
    if errors:
        raise AssertionError(f"{what}: {len(errors)} error slots, first: "
                             f"{errors[0]}")
    return np.stack(results)


def assert_equal(got: np.ndarray, want: np.ndarray, what: str) -> None:
    if got.shape != want.shape:
        raise AssertionError(f"{what}: shape {got.shape} != {want.shape}")
    bad = np.nonzero((got != want).any(axis=1))[0]
    if bad.size:
        raise AssertionError(f"{what}: {bad.size} of {got.shape[0]} egress "
                             f"rows differ from the oracle, first row "
                             f"{bad[0]}")
    log(f"{what}: {got.shape[0]} egress rows byte-equal to the oracle")


def assert_no_dispatch_faults(pipelines) -> None:
    for p in pipelines:
        for k in ("ingress_dispatch_failures_total",
                  "ingress_dispatch_retries_total"):
            if p.stats[k]:
                raise AssertionError(f"shard {p.shard_id}: {k} = "
                                     f"{p.stats[k]}")


def assert_kernels(engine) -> None:
    """Every compiled serving program holds the Pallas kernels."""
    progs = engine.compiled_programs()
    if not progs:
        raise AssertionError("no serving program was compiled")
    for key, prog in sorted(progs.items()):
        if "tpu_custom_call" not in prog.as_text():
            raise AssertionError(f"program {key} holds no Pallas kernel")
    log(f"Pallas kernels in every serving program: {sorted(progs)}")


def one_chip(args) -> None:
    import jax
    from repro.launch.serve import PacketServer

    t0 = time.perf_counter()
    raw, flow_feats, tenants = make_deployment(args.seed)
    log(f"trace {raw.shape[0]} packets / {N_FLOWS} flows, oracle flow "
        f"features and tenants in {time.perf_counter() - t0:.1f}s")

    srv = PacketServer(flow_capacity_pow2=FLOW_CAPACITY_POW2)
    install(srv, tenants)
    eng = srv.engine
    log(f"forest_variant={eng.forest_variant} "
        f"kernel_variant={eng.kernel_variant}")
    t0 = time.perf_counter()
    srv.warm()
    log(f"compiled {len(eng.compiled_programs())} serving programs in "
        f"{time.perf_counter() - t0:.1f}s")
    traces = eng.trace_count

    t0 = time.perf_counter()
    got_raw = stacked(serve_raw(srv, raw), "submit_raw")
    dt = time.perf_counter() - t0
    log(f"submit_raw served {got_raw.shape[0]} packets in {dt:.2f}s "
        "(host clock, compile excluded)")
    wire_mid, wire_x, wire = make_wire(args.seed)
    srv.submit_packets(wire)
    got_wire = stacked(srv.drain_packets(), "submit_packets")

    assert_no_dispatch_faults([srv.ingress])
    if eng.trace_count != traces:
        raise AssertionError(f"{eng.trace_count - traces} retraces after "
                             "warm-up")
    log("zero retraces after warm-up; zero dispatch retries or failures")
    assert_kernels(eng)
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    log("device memory: " + json.dumps(
        {k: stats[k] for k in ("bytes_in_use", "peak_bytes_in_use")
         if k in stats}))

    t0 = time.perf_counter()
    ref = Reference(srv.control_plane)
    mid_raw, x_raw = raw_inputs(raw, flow_feats, tenants)
    assert_equal(got_raw, ref.egress(x_raw, mid_raw), "submit_raw")
    assert_equal(got_wire, ref.egress(wire_x, wire_mid), "submit_packets")
    log(f"oracle in {time.perf_counter() - t0:.1f}s")


def four_chips(args) -> None:
    import jax
    from repro.serve import ShardedPacketServer

    raw, flow_feats, tenants = make_deployment(args.seed)

    def fabric(n):
        fab = ShardedPacketServer(n_shards=n,
                                  flow_capacity_pow2=FLOW_CAPACITY_POW2)
        install(fab, tenants)
        fab.warm()
        return fab

    one = fabric(1)
    got_one = stacked(serve_raw(one, raw), "1 shard")
    four = fabric(4)
    devs = [sh.engine.device for sh in four.shards]
    if len(set(devs)) != 4 or not set(devs) <= set(jax.devices()):
        raise AssertionError(f"shards are not on 4 distinct chips: {devs}")
    log(f"4 shards on {[str(d) for d in devs]}")
    traces = [sh.engine.trace_count for sh in four.shards]
    got_four = stacked(serve_raw(four, raw), "4 shards")
    assert_no_dispatch_faults([sh.pipeline for sh in four.shards])
    if [sh.engine.trace_count for sh in four.shards] != traces:
        raise AssertionError("retraces after warm-up on the 4-shard fabric")
    for sh in four.shards:
        assert_kernels(sh.engine)
    ref = Reference(four.control_plane)
    mid_raw, x_raw = raw_inputs(raw, flow_feats, tenants)
    want = ref.egress(x_raw, mid_raw)
    assert_equal(got_one, want, "1 shard")
    assert_equal(got_four, want, "4 shards")

    drill = fabric(4)
    half = (raw.shape[0] // 2) // CHUNK * CHUNK
    res = serve_raw(drill, raw, kill_at=half)
    if len(res) != raw.shape[0]:
        raise AssertionError(f"{len(res)} results for {raw.shape[0]} tickets")
    ok = np.asarray([isinstance(r, np.ndarray) for r in res])
    log(f"drill: shard {KILL_SHARD} killed at packet {half}; every "
        f"ticket resolved, {int((~ok).sum())} as error slots")
    assert_equal(np.stack([r for r in res if isinstance(r, np.ndarray)]),
                 want[ok], "drill, non-error rows")
    faults = drill.stats()["faults"]
    log(f"drill: deaths={faults['fabric_deaths_total']} "
        f"migrated_flows={faults['fabric_migrated_flows_total']}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    from repro.launch.compile_cache import enable as enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform} devices", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    log(f"{dev.device_kind} x{len(devices)}, jax {jax.__version__}, "
        f"compile cache {cache_dir}")
    (four_chips if args.chips == 4 else one_chip)(args)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
