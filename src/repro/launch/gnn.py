"""GNN embedding-serving driver over an ExecutionPlan.

The GNN analogue of ``launch.serve``: requests are node-embedding lookups
against a graph whose embeddings are refreshed by running the plan's forward
(centralized, decentralized, or semi-decentralized — paper Fig. 4 / §5), on
any of the kernel backends (``jnp``, ``pallas``, ``fused``). The fused
backend runs each layer's aggregation + crossbar MVM in a single kernel with
Z resident in VMEM (DESIGN.md §5), so every setting benefits — this is the
serving-path entry point the benchmark sweep and the examples drive.

  PYTHONPATH=src python -m repro.launch.gnn --setting semi --backend fused \
      --clusters 4 --sample 8 --requests 64

Streaming mode (``--stream N``) serves the same plan through
``repro.streaming.StreamingGNNServer``: N synthetic feature ticks are
ingested under the chosen refresh ``--policy``, embeddings refresh
incrementally over the k-hop dirty frontier, and the driver prints
recomputed-node fraction and measured incremental traffic (DESIGN.md §9):

  PYTHONPATH=src python -m repro.launch.gnn --setting decentralized \
      --stream 16 --churn 0.05 --policy bounded-staleness

``--plan auto`` delegates the configuration choice to the adaptive planner
(``repro.planner``, DESIGN.md §10): setting, backend, cluster count,
refresh policy, and neighbor mode come from the planner's recommendation
for this dataset's statistics and the requested churn/query workload.

Feature-similarity scenarios (``--dataset recsys|anomaly``) arrive as bare
feature vectors: the served graph is *built* by CAM-backed k-NN search
(``repro.neighbors``, DESIGN.md §15) on the ``--neighbor-mode`` path —
``cam`` / ``cam-pallas`` run associative band matching on the traversal
CAM kernel, ``topk`` the result-identical host fallback. In stream mode
the same flag routes dirty-frontier membership through the CAM
(``streaming.frontier``):

  PYTHONPATH=src python -m repro.launch.gnn --dataset recsys \
      --neighbor-mode cam --stream 8 --churn 0.05
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import telemetry as tel
from repro.core import costmodel, dataset_like, gnn
from repro.core.partition import ExecutionPlan, plan_execution
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh


class GNNServer:
    """Embedding server: refresh via the plan's forward, serve row lookups.

    Staleness is version-tracked: ``update_params`` / ``update_plan`` bump
    ``self.version``, and ``query`` refreshes whenever the served
    embeddings were computed at an older version (not only when they have
    never been computed). Mutating ``self.params`` in place bypasses the
    tracking — use the setters.
    """

    def __init__(self, plan: ExecutionPlan, cfg: gnn.GNNConfig,
                 params=None, mesh=None, seed: int = 0,
                 mode: str = "alltoall"):
        self.plan = plan
        self.cfg = plan.gnn_config(cfg)
        self.params = params if params is not None else gnn.init_params(
            jax.random.key(seed), self.cfg)
        self._mesh = mesh
        self._forward = None    # built lazily: subclasses that refresh
        #                         through another engine never pay for it
        self.mode = mode
        self.embeddings: np.ndarray | None = None
        self.refreshes = 0
        self.version = 0            # params/graph generation counter
        self._served_version = -1   # version the embeddings were built at

    def update_params(self, params) -> None:
        """Swap model parameters; served embeddings become stale."""
        self.params = params
        self.version += 1

    def update_plan(self, plan: ExecutionPlan, cfg=None) -> None:
        """Swap the execution plan (graph changed / repartitioned); rebuilds
        the forward and marks served embeddings stale."""
        cfg = cfg if cfg is not None else self.cfg
        self.plan = plan
        self.cfg = plan.gnn_config(cfg)
        self._forward = None
        self.version += 1

    @property
    def stale(self) -> bool:
        return self.embeddings is None or self._served_version != self.version

    def refresh(self) -> float:
        """Recompute all node embeddings; returns wall-clock seconds.

        Spans: ``server.refresh`` holds ``server.refresh.dispatch`` (the
        forward built on first use, then called up to its return),
        ``server.refresh.wait`` (until the device is done) and
        ``plan.scatter`` (the fetch to the host and the assembly in global
        order)."""
        t0 = time.perf_counter()
        with tel.span("server.refresh", setting=self.plan.setting):
            with tel.span("server.refresh.dispatch"):
                if self._forward is None:
                    self._forward = self.plan.make_forward(
                        self.cfg, mesh=self._mesh, mode=self.mode)
                out = self._forward(self.params)
            with tel.span("server.refresh.wait"):
                out = jax.block_until_ready(out)
            # bucketed plans return a tuple of ragged per-bucket tables;
            # scatter handles both shapes (np.asarray would choke on a tuple)
            self.embeddings = self.plan.scatter(out)
        self.refreshes += 1
        self._served_version = self.version
        return time.perf_counter() - t0

    def query(self, node_ids) -> np.ndarray:
        """Serve one batch of embedding lookups (refresh if stale).

        Batched: ids are validated against the *served* embedding table
        (out-of-range ids raise IndexError naming the offending bound —
        after ``update_plan`` to a smaller graph, stale ids fail loudly
        instead of wrapping); any batch shape gathers in one fancy index.
        """
        with tel.span("server.query"):
            if self.stale:
                self.refresh()
            ids = np.asarray(node_ids, np.int64)
            n = len(self.embeddings)
            if ids.size and (ids.min() < 0 or ids.max() >= n):
                raise IndexError(
                    f"node ids must be in [0, {n}); batch spans "
                    f"[{ids.min()}, {ids.max()}]")
            out = self.embeddings[ids]
            tel.counter("server.queries").inc(ids.size)
        return out


def stream_main(args, g, plan, cfg) -> None:
    """--stream driver: ingest a synthetic tick stream, serve batched
    lookups between commits, report incremental refresh statistics."""
    from repro.streaming import StreamingGNNServer
    frontier = {"topk": "numpy", "cam": "cam",
                "cam-pallas": "cam-pallas"}[args.neighbor_mode]
    srv = StreamingGNNServer(plan, cfg, mode=args.mode, policy=args.policy,
                             frontier_mode=frontier)
    t_cold = srv.refresh()
    print(f"plan: {args.setting}/{args.backend}, {g.n_nodes} nodes, "
          f"{plan.n_clusters} clusters; policy {args.policy}; "
          f"frontier membership via {frontier}; "
          f"cold full refresh {t_cold * 1e3:.1f} ms")
    rng = np.random.default_rng(0)
    served = 0
    inc_bytes = 0
    loop_commits = 0
    t0 = time.perf_counter()
    for tick in range(args.stream):
        n_mut = max(int(g.n_nodes * args.churn), 1)
        nodes = rng.choice(g.n_nodes, n_mut, replace=False)
        rows = rng.normal(size=(n_mut, g.feature_len)).astype(np.float32)
        upd = srv.ingest(nodes=nodes, rows=rows)
        if upd is not None:
            loop_commits += 1
            if upd.traffic is not None:
                inc_bytes += upd.traffic.total_bytes()
        served += len(srv.query(rng.integers(0, g.n_nodes, args.batch)))
    dt = time.perf_counter() - t0
    # the cold-start commit is a full refresh by construction — keep it out
    # of the incremental statistics it would otherwise bias
    fracs = [u.recompute_fraction for u in srv.updates if not u.full]
    print(f"{args.stream} ticks, {srv.commits} commits "
          f"({srv.full_refreshes} full), mean incremental recompute "
          f"fraction {float(np.mean(fracs)) if fracs else 1.0:.3f}")
    if plan.setting != "centralized" and loop_commits:
        print(f"measured incremental traffic {inc_bytes / 1e6:.3f} MB "
              f"(full-refresh equivalent "
              f"{plan.measured_traffic(srv.cfg, mode=args.mode).total_bytes() * loop_commits / 1e6:.3f} MB)")
    print(f"served {served} lookups alongside the stream in "
          f"{dt * 1e3:.1f} ms")


def _dump_telemetry(args) -> None:
    """--metrics / --trace exit dumps (telemetry enabled in main)."""
    if args.metrics:
        n = tel.export_metrics(args.metrics)
        print(f"telemetry: wrote {n} metric/event lines to {args.metrics}")
    if args.trace:
        n = tel.export_trace(args.trace)
        print(f"telemetry: wrote {n} span trees to {args.trace}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--setting", default="decentralized",
                    choices=("centralized", "decentralized", "semi"))
    ap.add_argument("--backend", default="fused",
                    choices=gnn.BACKENDS)
    ap.add_argument("--dataset", default="collab",
                    help="a Table-2 name / 'taxi' (dataset_like), or a "
                         "feature-similarity scenario 'recsys'/'anomaly' "
                         "whose graph is built by k-NN search "
                         "(repro.neighbors)")
    ap.add_argument("--scale", type=float, default=0.001)
    ap.add_argument("--neighbor-mode", default="topk", dest="neighbor_mode",
                    choices=("topk", "cam", "cam-pallas"),
                    help="neighbor selection / frontier membership path "
                         "(DESIGN.md §15): scenario k-NN construction and "
                         "stream-mode dirty-frontier tests run on the "
                         "traversal CAM ('cam' = jnp oracle kernel, "
                         "'cam-pallas' = Pallas kernel) or the "
                         "result-identical host fallback ('topk')")
    ap.add_argument("--clusters", type=int, default=0,
                    help="default: one per device (decentralized) / "
                         "4 heads (semi)")
    ap.add_argument("--spokes", type=int, default=4,
                    help="semi: member edge devices per cluster head")
    ap.add_argument("--mode", default="alltoall",
                    choices=("allgather", "alltoall"),
                    help="halo-exchange strategy (semi: tier-1)")
    ap.add_argument("--buckets", default="off", metavar="auto|off|N",
                    help="capacity-bucketed ragged layout (DESIGN.md §12): "
                         "'auto' buckets clusters by pow2 capacity, an int "
                         "caps the bucket count, 'off' keeps dense padding")
    ap.add_argument("--sample", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--mapping", action="store_true",
                    help="print the compiled crossbar mapping report "
                         "(DESIGN.md §8)")
    ap.add_argument("--tune", action="store_true",
                    help="autotune the plan's Pallas kernel launches "
                         "before serving (repro.tuning, DESIGN.md §11); "
                         "winners cache to --tune-cache")
    ap.add_argument("--tune-cache", default=None, metavar="PATH",
                    help="tuned-config cache file (default: "
                         "results/tuned_configs.json)")
    ap.add_argument("--stream", type=int, default=0, metavar="TICKS",
                    help="serve a TICKS-long synthetic feature stream "
                         "through StreamingGNNServer (incremental refresh)")
    ap.add_argument("--churn", type=float, default=0.05,
                    help="stream mode: fraction of nodes mutated per tick")
    ap.add_argument("--policy", default="eager",
                    choices=("eager", "interval", "bounded-staleness"),
                    help="stream mode: refresh policy")
    ap.add_argument("--plan", default="manual", dest="plan_mode",
                    choices=("manual", "auto"),
                    help="auto: let repro.planner pick setting/backend/"
                         "clusters/policy for this workload (DESIGN.md §10)")
    ap.add_argument("--tech", default=None, metavar="NAME[+NAME]",
                    help="device technology for the derived cost/mapping "
                         "reports (sot-mram, reram, sram, fefet; "
                         "DESIGN.md §13); a 'spoke+head' pair like "
                         "'reram+sram' bills ReRAM spoke storage under "
                         "SRAM cluster heads (semi setting); with "
                         "--plan auto the planner searches within it")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="enable telemetry; dump the metrics registry "
                         "(counters/gauges/histograms + audit events) as "
                         "JSONL to PATH on exit (DESIGN.md §14)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="enable telemetry; export the recorded span trees "
                         "as JSONL to PATH on exit")
    args = ap.parse_args()
    enable_compile_cache()
    if args.metrics or args.trace:
        tel.enable()

    tech = None
    if args.tech:
        tech = (tuple(args.tech.split("+")) if "+" in args.tech
                else args.tech)
        from repro.devices import resolve_technology
        for t in (tech if isinstance(tech, tuple) else (tech,)):
            resolve_technology(t)       # typos fail here, by name
    from repro.neighbors import SCENARIOS, scenario_graph
    if args.dataset in SCENARIOS:
        g = scenario_graph(
            args.dataset, n_nodes=max(int(200_000 * args.scale), 32),
            feature_len=32, k=args.sample,
            neighbor_mode="topk" if args.neighbor_mode == "topk" else "cam",
            backend="pallas" if args.neighbor_mode == "cam-pallas"
            else "jnp").gcn_normalize()
        print(f"{args.dataset}: built k-NN graph on the "
              f"{args.neighbor_mode} path — {g.n_nodes} nodes, "
              f"{g.n_edges} similarity edges")
    else:
        g = dataset_like(args.dataset, scale=args.scale,
                         seed=0).gcn_normalize()
    if args.plan_mode == "auto":
        from repro.planner import WorkloadProfile, plan as plan_search
        wl = WorkloadProfile(
            churn=args.churn if args.stream else 0.0,
            queries_per_tick=float(args.batch),
            sample=args.sample)
        objective = "throughput" if args.stream else "latency"
        result = plan_search(g, objective, workload=wl, shortlist=2,
                             **(dict(technologies=(tech,)) if tech else {}))
        print(result.summary())
        rec = result.recommended.candidate
        args.setting, args.backend = rec.setting, rec.backend
        args.clusters, args.policy = rec.n_clusters, rec.policy
        if args.neighbor_mode != "cam-pallas":
            # keep an explicit pallas request; otherwise follow the
            # planner's priced neighbor_mode axis
            args.neighbor_mode = rec.neighbor_mode
    n_dev = len(jax.devices())
    k = args.clusters or (n_dev if args.setting == "decentralized" else 4)
    buckets = args.buckets if args.buckets in ("auto", "off") \
        else int(args.buckets)
    plan = plan_execution(g, args.setting, backend=args.backend,
                          sample=args.sample,
                          n_clusters=None if args.setting == "centralized"
                          else k,
                          spokes_per_head=args.spokes,
                          buckets=buckets)
    mesh = (make_mesh((n_dev,), ("data",))
            if plan.n_clusters == n_dev and args.setting != "centralized"
            and plan.bucketed is None else None)
    if plan.bucketed is not None:
        ls = plan.layout_stats()
        print(f"bucketed layout: {plan.bucketed.n_buckets} buckets, "
              f"caps {plan.bucketed.n_caps}; padding ratio "
              f"{ls['padding_ratio']:.2f}x vs dense "
              f"{ls['dense_padding_ratio']:.2f}x, peak device bytes "
              f"{ls['peak_device_bytes']:,} vs dense "
              f"{ls['dense_peak_device_bytes']:,}")
    cfg = gnn.GNNConfig(in_dim=g.feature_len, hidden_dims=(args.hidden,),
                        out_dim=16, sample=args.sample)
    if args.tune:
        from repro.tuning import DEFAULT_CACHE_PATH, TuneCache
        cache = TuneCache.load(args.tune_cache or DEFAULT_CACHE_PATH)
        tuned = plan.tune_kernels(cfg, cache=cache)
        print(f"autotuned {len(tuned)} kernel geometries "
              f"(cache: {cache.path}, {len(cache)} entries)")
    if args.stream:
        stream_main(args, g, plan, cfg)
        return _dump_telemetry(args)
    srv = GNNServer(plan, cfg, mesh=mesh, mode=args.mode)

    dt = srv.refresh()
    print(f"plan: {args.setting}/{args.backend}, {g.n_nodes} nodes, "
          f"{plan.n_clusters} clusters on {n_dev} devices; "
          f"embedding refresh {dt * 1e3:.1f} ms")
    if args.setting != "centralized":
        print("measured traffic —",
              plan.measured_traffic(cfg, mode=args.mode).summary())

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    served = 0
    for _ in range(args.requests):
        ids = rng.integers(0, g.n_nodes, args.batch)
        out = srv.query(ids)
        served += len(ids)
    dt = time.perf_counter() - t0
    print(f"served {served} lookups in {dt * 1e3:.1f} ms "
          f"({served / dt:.0f} lookups/s)")

    # a per-tier pair prices the mapper with the head (compute) tier; the
    # spoke tier only bills storage energy, which the planner accounts
    head_tech = tech[-1] if isinstance(tech, tuple) else tech
    m = plan.predicted_metrics(**(dict(mode="derived", technology=head_tech)
                                  if tech else {}))
    label = f"{args.setting}, {args.tech}" if tech else args.setting
    print(f"cost model ({label}): T_compute {m.t_compute:.3e} s, "
          f"T_comm {m.t_communicate:.3e} s, P {m.p_net * 1e3:.1f} mW")
    mapping = plan.compile_mapping(cfg, technology=head_tech)
    print(f"mapper-derived T_compute {mapping.t_compute:.3e} s "
          f"({mapping.t_compute / max(m.t_compute, 1e-30):.2f}x calibrated); "
          f"run with --mapping for the full report")
    if args.mapping:
        print(plan.mapping_report())    # reuses the cached mapping
    best, _ = costmodel.pick_setting(g.stats(args.dataset),
                                     n_clusters=plan.n_clusters)
    print(f"cost-model guideline for this graph: {best}")
    _dump_telemetry(args)


if __name__ == "__main__":
    main()
