"""One run of one cell: set-up, the measured window, the check, the metrics.

What a run does, in order:

1. Checks that JAX's first device is a TPU and that there are as many as
   the cell asks for; otherwise it prints why and exits non-zero. It never
   falls back to the CPU.
2. Set-up (``setup_s``, from process start to the window's first instant):
   the graph (``graphs.csr``, from the configuration's structure seed), the
   features (from the seed, drawn on the device), the program's plan
   (``plan_execution``), the server (``GNNServer``, on a mesh for a cell of
   several chips), a first update, which compiles, and a warm one.
3. The window, ``--seconds`` long: updates run back to back, each
   ``update_params`` with fresh weights from the seed and ``refresh()``
   (the plan's forward on the chip, then ``scatter`` to the host table);
   between updates one thread answers, in due order, the lookups
   (``query``) that came due, from the traffic mix's schedule. The window
   closes at the first update boundary after ``--seconds``.
4. After it: the device's peak memory, then the program is freed and the
   plain reference (``reference.py``) recomputes a sample of the window's
   updates, drawn from the seed, to decide ``correct``; every lookup's rows
   are compared with the table it was served from.
5. The metrics: with ``--trace 0`` the cell's end-to-end metrics, with
   ``--trace 1`` its per-layer ones from a profiler trace of the window.
   Each is read by ``bench/metrics/<name>.py``.

The last line of standard output is the result; the last lines of
standard error are the numbers compared, each beside its limit.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from bench import graphs, loadgen, peaks, reference, trace, work

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
N_CHECKED = 3           # updates of the window the reference recomputes
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


class NoChip(RuntimeError):
    pass


def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_of(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r}; known: "
                   f"{[w['name'] for w in spec['workloads']]}")


def config_of(spec: dict, name: str, root: str = ROOT) -> dict:
    for c in spec["configs"]:
        if c["name"] == name:
            with open(os.path.join(root, c["file"])) as f:
                return json.load(f)
    raise KeyError(f"no config {name!r}")


def metric_names(spec: dict, cell: str, traced: bool) -> list:
    """The metrics a run of ``cell`` reports: end-to-end ones untraced,
    per-layer ones traced; a metric with ``workloads`` only in those."""
    group = spec["per_layer"] if traced else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def load_reader(name: str, root: str = HERE):
    path = os.path.join(root, "metrics", f"{name}.py")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def check_devices(chips: int) -> list:
    """The first ``chips`` TPU devices, or ``NoChip``."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devs[0].platform!r}, not a "
                     f"TPU; the benchmark measures only on the chip")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at the fixed ``<checkout>/
    .jax_cache``, or where ``JAX_COMPILATION_CACHE_DIR`` says."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@dataclasses.dataclass
class Records:
    """What a run measured; the metric readers read it."""
    chips: int
    setup_s: float
    window_s: float
    n_updates: int
    lookup_due: np.ndarray        # [M] s from window start
    lookup_start: np.ndarray      # [M]
    lookup_end: np.ndarray        # [M]
    model_flops: float            # per update
    work: list                    # per device, per layer {"flops", "bytes"}
    peaks: dict
    trace: dict | None = None     # trace.reduce() of the window


class _Compiles:
    """Counts compilations while ``on`` (one listener per process)."""
    on, n = False, 0

    def __call__(self, event, duration, **kw):
        if self.on and event in COMPILE_EVENTS:
            self.n += 1


_COMPILES = None


def _compiles() -> _Compiles:
    global _COMPILES
    if _COMPILES is None:
        import jax
        _COMPILES = _Compiles()
        jax.monitoring.register_event_duration_secs_listener(_COMPILES)
    _COMPILES.on, _COMPILES.n = False, 0
    return _COMPILES


def _say(*a):
    print(*a, file=sys.stderr, flush=True)


def run_cell(spec: dict, cell: dict, cfg: dict, mix: dict, seed: int,
             seconds: float, traced: bool, devices: list,
             t_start: float) -> tuple:
    """One run; returns ``(result dict, check lines)``."""
    import jax
    from repro.core import gnn
    from repro.core.graph import Graph
    from repro.core.partition import plan_execution
    from repro.kernels.crossbar_mvm import CrossbarNumerics
    from repro.launch.gnn import GNNServer
    from repro.launch.mesh import make_mesh

    clock = time.perf_counter
    setup = {"start_and_devices_s": time.monotonic() - t_start}
    s_feat, s_wts, s_traffic, s_check = graphs.seeds(seed, 4)
    gcfg, mcfg = cfg["graph"], cfg["model"]
    dims = (mcfg["in_dim"], *mcfg["hidden_dims"], mcfg["out_dim"])
    n_nodes, sample = gcfg["nodes"], mcfg["sample"]
    compiles = _compiles()

    t = clock()
    g = graphs.csr(n_nodes, gcfg["edges"], gcfg["structure_seed"],
                   gcfg["min_in_degree"])
    edge_w, self_w = graphs.gcn_weights(g)
    setup["graph_s"] = clock() - t
    t = clock()
    x = graphs.features(graphs.jax_key(s_feat), n_nodes, gcfg["features"])
    setup["features_s"] = clock() - t

    t = clock()
    k = cfg["clusters"]
    plan = plan_execution(
        Graph(g["indptr"], g["indices"], edge_w, x, self_w),
        cfg["setting"], backend=cfg["backend"], sample=sample,
        n_clusters=None if cfg["setting"] == "centralized" else k,
        seed=cfg["partition_seed"])
    setup["plan_s"] = clock() - t

    t = clock()
    mesh = (make_mesh((k,), ("data",)) if cell["chips"] > 1 else None)
    model = gnn.GNNConfig(in_dim=dims[0], hidden_dims=tuple(dims[1:-1]),
                          out_dim=dims[-1], sample=sample,
                          numerics=CrossbarNumerics(
                              ideal=cfg["numerics"] == "ideal"))
    params_fn = graphs.make_params_fn(dims)
    wkey = graphs.jax_key(s_wts)
    srv = GNNServer(plan, model, params=params_fn(wkey, np.int32(0)),
                    mesh=mesh, mode=cfg["exchange"] or "alltoall")
    srv.refresh()
    setup["compile_and_first_update_s"] = clock() - t
    t = clock()
    srv.update_params(params_fn(wkey, np.int32(1)))
    srv.refresh()
    srv.query(np.arange(min(int(mix["lookups"]["batch"]), n_nodes)))
    setup["warm_update_s"] = clock() - t

    horizon = 2.0 * seconds + 30.0
    due, ids = loadgen.lookup_schedule(mix, s_traffic, horizon, n_nodes)
    span = contextlib.nullcontext
    logdir = None
    if traced:
        span = jax.profiler.TraceAnnotation
        scatter = plan.scatter

        def traced_scatter(out):
            with span("bench.scatter"):
                return scatter(out)
        plan.scatter = traced_scatter
        logdir = tempfile.mkdtemp(prefix="bench-trace-")
        trace.capture(logdir)

    tables, upd, served = [], [], []     # served: (table, rows, start, end)

    def serve_due(until: float) -> None:
        while len(served) < len(due) and due[len(served)] <= until:
            j = len(served)
            s = clock() - t0
            rows = srv.query(ids[j])
            served.append((len(tables) - 1, rows, s, clock() - t0))

    i = 0
    gc.collect()
    gc.freeze()         # no collector pauses over set-up's objects
    compiles.on = True
    t0 = clock()
    setup_s = time.monotonic() - t_start
    with span("bench.window"):
        while True:
            tb = clock() - t0
            if tb >= seconds:
                break
            with span("bench.update"):
                srv.update_params(params_fn(wkey, np.int32(i + 2)))
                srv.refresh()
            upd.append((tb, clock() - t0))
            tables.append(srv.embeddings)
            i += 1
            with span("bench.serve"):
                serve_due(clock() - t0)
    window_s = clock() - t0
    compiles.on = False
    gc.unfreeze()
    n_upd = len(upd)
    serve_due(window_s)         # due in the window, answered after it
    n_lk = len(served)
    lk_start = [sv[2] for sv in served]

    red = None
    if traced:
        red = trace.reduce(trace.stop(logdir))
        shutil.rmtree(logdir, ignore_errors=True)
        names = {f"{trace.DEVICE_PREFIX}{d.id}" for d in devices}
        used = {k_: v for k_, v in red["devices"].items() if k_ in names}
        red["devices"] = used or red["devices"]

    mem_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices)
    assignment = (plan.part.assignment.copy() if plan.part is not None
                  else np.zeros(n_nodes, np.int32))
    del srv, plan
    gc.collect()

    _say(f"setup: " + ", ".join(f"{k_} {v:.3f}" for k_, v in setup.items())
         + f"; setup_s {setup_s:.3f}")
    lag = (np.array(lk_start) - due[:n_lk]) if n_lk else np.zeros(1)
    slowest = max((e - b for b, e in upd), default=0.0)
    _say(f"window: {window_s:.3f} s, {n_upd} updates, {n_lk} lookups due "
         f"(schedule fixed before the window from the seed, so the "
         f"generator is never late); wait before service median "
         f"{np.median(lag) * 1e3:.3f} ms, max {lag.max() * 1e3:.3f} ms; "
         f"slowest update {slowest * 1e3:.3f} ms; "
         f"compilations in the window: {compiles.n}")

    t = clock()
    nbr, wts = graphs.sample_table(g, edge_w, self_w, sample)
    checks, failed = _check(cfg, nbr, wts, x, params_fn, wkey, tables,
                            served, ids, s_check)
    _say(f"reference and comparison: {clock() - t:.3f} s (after the "
         f"window, not in setup_s)")

    rows = work.device_rows(nbr, wts, assignment, k if cell["chips"] > 1
                            else 1)
    kind = devices[0].device_kind
    rec = Records(
        chips=cell["chips"], setup_s=setup_s, window_s=window_s,
        n_updates=n_upd,
        lookup_due=due[:n_lk], lookup_start=np.array(lk_start),
        lookup_end=np.array([sv[3] for sv in served]),
        model_flops=work.model_flops(n_nodes, sample, dims),
        work=work.update_work(dims, sample, rows), peaks=peaks.peaks_for(kind),
        trace=red)
    metrics = {}
    for m_ in metric_names(spec, cell["name"], traced):
        v = load_reader(m_["name"])(rec)
        if v is not None:
            metrics[m_["name"]] = {"value": float(v), "unit": m_["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": n_upd + n_lk, "failed": failed,
              "metrics": metrics, "device": device}
    if red is not None:
        lo, hi = trace.window(red)
        busy = trace.busy_ns(red)
        device["busy_s"] = sum(busy.values()) / max(len(busy), 1) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = {"device_ops": trace.top_ops(red),
                               "idle_gaps": trace.idle_gaps(red)}
    result["checks"] = checks
    lines = [f"check {n}: {c['value']!r} (limit {c['limit']!r})"
             for n, c in checks.items()]
    return result, lines


def _check(cfg, nbr, wts, x, params_fn, wkey, tables, served, ids,
           s_check) -> tuple:
    """The numbers compared, each with its limit, and how many answers of
    the window were wrong. The reference recomputes ``N_CHECKED`` updates
    drawn from the seed, the last one always among them; every lookup's
    rows are compared with the table it was served from."""
    limits = cfg["checks"]
    n_upd = len(tables)
    rng = np.random.default_rng(s_check)
    picked = sorted({n_upd - 1, *rng.choice(
        max(n_upd - 1, 0), size=min(N_CHECKED - 1, max(n_upd - 1, 0)),
        replace=False).tolist()}) if n_upd else []
    params = [params_fn(wkey, np.int32(u + 2)) for u in picked]
    refs = reference.forward(x, nbr, wts, params, reference.JUDGED)
    rms = mx = 0.0 if picked else float("inf")
    bad_updates = 0
    for u, ref in zip(picked, refs):
        r, a = reference.gaps(tables[u], ref)
        bad_updates += r > limits["embed_rms_rel"]
        rms, mx = max(rms, r), max(mx, a)
    _say(f"widest gap of the checked updates (not judged: it swings from "
         f"seed to seed and the control does not read 3x it): {mx:.4e} of "
         f"max|ref|")
    if picked:
        f32 = reference.forward(x, nbr, wts, params[-1:], "float32")[0]
        _, a32 = reference.gaps(tables[picked[-1]], f32)
        _say(f"update {picked[-1]} against the float32 reference at the "
             f"highest precision (not judged): max gap {a32:.4e} of max|ref|")
    wrong = sum(not np.array_equal(rows, tables[t][ids[j]])
                for j, (t, rows, _, _) in enumerate(served))
    checks = {
        "embed_rms_rel": {"value": rms, "limit": limits["embed_rms_rel"]},
        "lookups_wrong": {"value": wrong, "limit": 0},
    }
    return checks, int(bad_updates + wrong)


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.monotonic() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_spec()
    cell = cell_of(spec, args.workload)
    cfg = config_of(spec, cell["config"])
    mix = loadgen.load_mix(cell["traffic"])
    try:
        devices = check_devices(cell["chips"])
    except NoChip as e:
        _say(f"bench: {e}")
        return 3
    enable_compile_cache()
    result, lines = run_cell(spec, cell, cfg, mix, args.seed, args.seconds,
                             bool(args.trace), devices, t_start)
    for line in lines:
        _say(line)
    print(json.dumps(result), flush=True)
    return 0
