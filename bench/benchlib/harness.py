"""One run of one cell: discovery by name, set-up, the measured window,
the check against the reference, and the metric readers.

Everything a cell needs is found by name from files alone:

* the cell and the metric declarations in ``BENCHMARK.json``;
* the configuration in the file its ``configs`` entry names;
* the traffic mix in ``bench/traffic/<traffic>.json``, a file of
  parameters;
* the mix's generator in ``bench/generators/<generator>.py`` (a
  ``build(mix, cfg, seed, seconds)`` that returns a ``gen.Traffic``);
* the mix's client loop in ``bench/loops/<loop.kind>.py`` (a
  ``run(srv, traffic, params, seconds, spans)`` that returns a
  ``loop.WindowResult``);
* each metric's reader in ``bench/metrics/<metric>.py`` (a ``read(ctx)``
  that returns a number, or ``None`` when it finds nothing to read); a
  metric ``<base>.<suffix>`` with no file of its own reads as ``<base>``.

A later cell, mix, generator, loop or metric is a new file and a new
entry; no file here needs an edit.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import time
import types
from pathlib import Path
from typing import Optional

import numpy as np

from . import deploy, loop, tracefile, work
from .reference import Reference, parse_wire

ROOT = Path(__file__).resolve().parents[2]


# -- discovery ----------------------------------------------------------------

def load_benchmark(root: Path) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def load_config(root: Path, bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            with open(root / c["file"]) as f:
                return json.load(f)
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def load_mix(root: Path, name: str) -> dict:
    with open(root / "bench" / "traffic" / f"{name}.json") as f:
        return json.load(f)


def load_plugin(root: Path, kind: str, name: str):
    """The module ``bench/<kind>/<name>.py`` under ``root``."""
    path = root / "bench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(root: Path, name: str):
    """The reader ``bench/metrics/<name>.py``; a metric split by suffix
    (``<base>.<suffix>``, one quantity reported in cells that move
    different end-to-end metrics) without a file of its own reads as
    ``<base>``."""
    if not (root / "bench" / "metrics" / f"{name}.py").exists():
        name = name.split(".")[0]
    return load_plugin(root, "metrics", name).read


def require_chips(n: int) -> Optional[str]:
    """Why this machine cannot run an ``n``-chip cell, or ``None``."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return f"no TPU: JAX found {devices[0].platform} devices"
    if len(devices) < n:
        return f"the cell needs {n} chips, JAX found {len(devices)}"
    return None


def cell_metrics(bench: dict, cell: str, trace: bool) -> list:
    """The metrics a cell reports in this kind of run: its end-to-end
    metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}

    def here(m):
        if "workloads" in m:
            return cell in m["workloads"]
        return m["moves"] in names
    return [m for m in bench["per_layer"] if here(m)]


# -- counters -----------------------------------------------------------------

def read_counters(srv) -> dict:
    """Totals of the registry's counters, summed over labels (shards)."""
    snap = srv.obs.registry.snapshot()
    out = {}
    for name, val in snap.items():
        if isinstance(val, dict) and val and all(
                isinstance(v, (int, float)) for v in val.values()):
            out[name] = float(sum(val.values()))
        elif isinstance(val, (int, float)):
            out[name] = float(val)
    return out


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a.get(k, 0.0) for k in b}


class GcTime:
    """Seconds the cyclic garbage collector ran, and its full passes, while
    registered in ``gc.callbacks``."""

    def __init__(self):
        self.seconds = 0.0
        self.full = 0
        self._t0 = 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.seconds += time.perf_counter() - self._t0
            self.full += info["generation"] == 2


# -- one run ------------------------------------------------------------------

def _mids(traffic, n: int) -> np.ndarray:
    """Model id of each of the first ``n`` window packets."""
    rows = traffic.rows
    col = (13, 14) if traffic.surface == "raw" else (0, 1)
    mid = (rows[:, col[0]].astype(np.int64) << 8) | rows[:, col[1]]
    if traffic.cyclic:
        p = mid.shape[0]
        return np.concatenate([np.tile(mid, n // p), mid[: n % p]])
    return mid[:n]


def _check(cfg, tenants, traffic, res) -> dict:
    """Compare every sampled answer with the reference."""
    ref = Reference(cfg, tenants)
    got = res.sample_rows
    pos = res.sample_pos
    if pos.size == 0:
        return {"compared": 0, "mismatched": 0}
    if traffic.surface == "raw":
        stream = np.concatenate([traffic.setup_raw,
                                 traffic.rows[: res.attempted]])
        at = traffic.setup_raw.shape[0] + pos
        feats = ref.flow_features(stream, at)
        rows = stream[at]
        mid = ((rows[:, 13].astype(np.int32) << 8) | rows[:, 14])
        want = ref.egress(ref.gather(feats, mid), mid,
                          np.zeros(mid.shape[0], np.int32))
    else:
        mid, flags, x = parse_wire(traffic.row_at(pos), ref.width)
        want = ref.egress(x, mid, flags)
    if got.shape != want.shape:
        return {"compared": int(pos.size), "mismatched": int(pos.size)}
    bad = int((got != want).any(axis=1).sum())
    return {"compared": int(pos.size), "mismatched": bad}


def _work(cfg, tenants, traffic, res, counters) -> dict:
    """Needed work of the window's answered packets (see ``work``)."""
    srv = cfg["server"]
    depth = srv["max_tree_depth"]
    by_id = {t["id"]: t for t in tenants}
    counts = np.bincount(_mids(traffic, res.attempted),
                         minlength=max(by_id) + 1)
    pk = counters.get("ingress_packets_total", 0.0)
    short = (counters.get("ingress_cache_hits_total", 0.0)
             + counters.get("ingress_coalesced_total", 0.0))
    computed = 1.0 - (short / pk if pk else 0.0)
    out = {"answered_ops": 0.0, "computed_share": computed}
    for lane in ("mlp", "forest"):
        n = sum(int(counts[i]) for i, t in by_id.items() if t["kind"] == lane)
        ops = sum(int(counts[i]) * work.tenant_ops(t, depth)
                  for i, t in by_id.items() if t["kind"] == lane)
        out["answered_ops"] += ops
        out[lane] = {"rows": n * computed, "ops": ops * computed,
                     "row_bytes": n * computed * work.row_bytes(
                         srv["max_width"]),
                     "table_bytes": work.table_bytes(
                         tenants, lane, srv["weight_bits"])}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, t_start: Optional[float] = None,
             overrides: Optional[dict] = None, log=None) -> dict:
    """Set up, measure and check one run; returns the result line.

    ``overrides`` replaces server settings (the control runs the int8
    lane this way); the benchmark's own runs use none."""
    t_start = time.perf_counter() if t_start is None else t_start
    log = log or (lambda m: print(m, file=sys.stderr, flush=True))
    import jax
    bench = load_benchmark(root)
    cell = find_cell(bench, workload)
    cfg = load_config(root, bench, cell["config"])
    mix = load_mix(root, cell["traffic"])
    generator = load_plugin(root, "generators", mix["generator"])
    client_loop = load_plugin(root, "loops", mix["loop"]["kind"])
    readers = [(m, load_reader(root, m["name"]))
               for m in cell_metrics(bench, workload, trace)]
    chunk = mix["loop"]["chunk"]
    loop_params = dict(mix["loop"], seed=seed)

    phases = {}
    t = time.perf_counter()
    tenants = deploy.make_tenants(cfg, seed, mix.get("feature_lanes"))
    srv = deploy.build_server(cfg, overrides)
    deploy.install(srv, cfg, tenants)
    phases["server"] = time.perf_counter() - t
    t = time.perf_counter()
    traffic = generator.build(mix, cfg, seed, seconds)
    phases["traffic"] = time.perf_counter() - t
    t = time.perf_counter()
    srv.warm()
    phases["compile"] = time.perf_counter() - t
    t = time.perf_counter()
    setup_failed = (loop.submit_all(srv, traffic.setup_wire, "wire", chunk)
                    + loop.submit_all(srv, traffic.setup_raw, "raw", chunk))
    phases["warm_traffic"] = time.perf_counter() - t
    # Set-up's objects (modules, the server, the traffic) live for the whole
    # run: freeze them, so that a full collection inside the window walks
    # only what the window allocated.
    gc.collect()
    gc.freeze()

    trace_dir = None
    if trace:
        trace_dir = root / ".bench_cache" / "trace" / workload
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    c0 = read_counters(srv)
    gc_time = GcTime()
    gc.callbacks.append(gc_time)
    setup_s = time.perf_counter() - t_start
    if trace:
        with jax.profiler.TraceAnnotation("bench.window"):
            res = client_loop.run(srv, traffic, loop_params, seconds,
                                  loop.Spans(True))
        jax.profiler.stop_trace()
    else:
        res = client_loop.run(srv, traffic, loop_params, seconds,
                              loop.Spans(False))
    gc.callbacks.remove(gc_time)
    counters = _delta(c0, read_counters(srv))

    devices = jax.devices()[: cell["chips"]]
    peak_mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
    gc.unfreeze()
    del srv
    gc.collect()

    log(f"set-up {setup_s:.3f}s: " + ", ".join(
        f"{k} {v:.3f}s" for k, v in phases.items()))
    log(f"window {res.seconds:.3f}s, {res.iterations} iterations, "
        f"{res.answered} answered of {res.attempted}, "
        f"retraces in window {counters.get('engine_retraces_total', 0):.0f}")
    t = time.perf_counter()
    chk = _check(cfg, tenants, traffic, res)
    log(f"reference check {time.perf_counter() - t:.3f}s")
    red = None
    if trace:
        t = time.perf_counter()
        red = tracefile.reduce(*tracefile.load_events(
            tracefile.find_xplane(str(trace_dir))))
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace reduced in {time.perf_counter() - t:.3f}s: window "
            f"{red['window_s']:.3f}s, busy {red['busy_s']:.6f}s")

    dev0 = devices[0]
    ctx = types.SimpleNamespace(
        chips=cell["chips"], res=res,
        window_s=res.seconds, setup_s=setup_s, counters=counters,
        trace=red, work=_work(cfg, tenants, traffic, res, counters),
        peaks=work.peaks(dev0.device_kind) if dev0.platform == "tpu"
        else None)
    metrics = {}
    for m, read in readers:
        v = read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    checks = {
        "mismatched_rows": {"value": chk["mismatched"], "limit": 0,
                            "rule": "<="},
        "unanswered": {"value": res.failed + setup_failed, "limit": 0,
                       "rule": "<="},
        "compared_rows": {"value": chk["compared"],
                          "limit": mix["min_compared"], "rule": ">="},
    }
    correct = all(c["value"] <= c["limit"] if c["rule"] == "<="
                  else c["value"] >= c["limit"] for c in checks.values())
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['rule']} {c['limit']})")
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak_mem)}
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
    out = {"correct": bool(correct), "attempted": int(res.attempted),
           "failed": int(res.failed), "metrics": metrics, "device": device}
    if red is not None:
        out["breakdown"] = tracefile.breakdown(red)
    out["loop"] = {"iterations": res.iterations,
                   "longest_iteration_s": res.longest_iteration_s,
                   "gc_s": gc_time.seconds, "gc_full_passes": gc_time.full,
                   "retraces": counters.get("engine_retraces_total", 0.0),
                   "setup_phases_s": phases, **res.notes}
    out["checks"] = checks
    return out


def set_cache_env(root: Path) -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (the program honours ``JAX_COMPILATION_CACHE_DIR``), and the
    TPU runtime's logs beside it.  Call before JAX is imported."""
    cache = root / ".bench_cache"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache / "jax")
    # keep every serving program, however fast it compiled
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    (cache / "tpu_logs").mkdir(parents=True, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", str(cache / "tpu_logs"))
    sys.path.insert(0, str(root / "src"))
