"""Incremental GNN forward over an ExecutionPlan: recompute only the dirty
frontier, reuse cached per-layer activations for everything else.

``IncrementalEngine`` wraps an ``ExecutionPlan`` (any setting ×
any backend) and maintains:

  * the evolving ``Graph`` (mutated via ``streaming.delta``),
  * cached per-layer activations in the plan's owned-row layout
    ``[K, n_max, F_l]`` for levels 0..L (level 0 is the input table — for
    semi this is the tier-0-assembled region table),
  * the plan's structural tables, rebuilt in place on edge deltas with the
    *same* cluster assignment (nodes never migrate mid-stream, so the
    caches stay row-aligned; only the halo/send tables change).

Per tick, ``apply_delta`` commits the mutation buffer, expands the k-hop
dirty frontier (``streaming.frontier``), and re-runs each layer only on its
dirty rows — through the exact same layer step (``core.gnn.layer_step``)
every backend-setting combination uses, so incremental output matches a
full recompute to fp32 tolerance (the property ``tests/test_streaming.py``
checks on all 3 × 3 combinations). A full refresh runs that step per
cluster through ``distributed.halo._bucket_layer``, the dense layout as one
bucket of every cluster, with the halo of ``halo._emulated_exchange``.
Halo inputs for dirty rows are gathered from the cached level-(l-1) owned
tables; the wire traffic a real deployment would ship for that gather —
only rows whose value changed, plus send slots structural churn newly
created — is billed by ``distributed.traffic.measure_incremental``.

Degradation to full refresh (DESIGN.md §9): bit-accurate crossbar numerics
(``cfg.numerics.ideal=False``) quantize against a *global* DAC scale
``max|Z|``, so a subset recompute would see a different scale than a full
pass and drift; the engine detects this and falls back to a full refresh
(``StreamingUpdate.full=True``) rather than serve non-reproducible
embeddings.

Dirty row counts vary every tick; to keep JIT recompilation bounded the
engine buckets the recompute batch to the next power of two (padded rows
are sliced off), so at most O(log n_max) variants per (layer, cluster
shape) ever compile.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry as tel
from repro.core.gnn import layer_step
from repro.core.partition import (ExecutionPlan, _from_assignment,
                                  bucket_partition, build_bucketed_subgraphs,
                                  build_local_subgraphs,
                                  gather_bucketed_features)
from repro.distributed.halo import (HaloPlan, _bucket_layer,
                                    _emulated_exchange, _flat_rows,
                                    _gather_halo, build_bucketed_halo_plan,
                                    build_halo_plan, exchange_tables)
from repro.distributed.traffic import (StreamingTrafficReport,
                                       measure_incremental)
from repro.streaming.delta import DeltaResult, GraphDelta, apply_deltas
from repro.streaming.frontier import FrontierMasks, expand_frontier

_MIN_BUCKET = 8


def _bucket(n: int, cap: int) -> int:
    """Next power of two >= n (>= _MIN_BUCKET), capped at the table size."""
    b = _MIN_BUCKET
    while b < n:
        b <<= 1
    return min(b, cap)


def _pad_rows(rows: np.ndarray, cap: int) -> np.ndarray:
    """Bucket-pad a dirty-row batch by repeating its first row, so the
    scatter's shape — and hence its compiled executable — is reused across
    ticks (pad rows recompute the same value; the duplicate scatter is
    benign)."""
    padded = np.full(_bucket(len(rows), cap), rows[0], np.int64)
    padded[:len(rows)] = rows
    return padded


_rows_step = jax.jit(layer_step, static_argnames=("cfg", "act"))

# the activation-cache patch: the cache buffer is DONATED — the scatter's
# output aliases the input's pages, so per-tick updates mutate the
# device-resident cache in place instead of round-tripping a fresh
# allocation through the host every tick (DESIGN.md §12). Callers must
# rebind (``self._acts[..] = _scatter_rows(self._acts[..], ...)``) and
# never hold a second reference to the donated buffer.
_scatter_rows = jax.jit(
    lambda acts, c, rows, vals: acts.at[c, rows].set(vals),
    donate_argnums=(0,))


@dataclasses.dataclass
class StreamingUpdate:
    """Outcome of one committed tick."""
    frontier: FrontierMasks
    traffic: StreamingTrafficReport | None   # None for centralized
    seconds: float                           # wall-clock of the commit
    full: bool                               # True => degraded to full refresh

    @property
    def recompute_fraction(self) -> float:
        return 1.0 if self.full else self.frontier.recompute_fraction()


class IncrementalEngine:
    """Streaming counterpart of ``ExecutionPlan.make_forward``."""

    def __init__(self, plan: ExecutionPlan, cfg, params,
                 mode: str = "alltoall", frontier_mode: str = "numpy"):
        from repro.streaming.frontier import FRONTIER_MODES
        if frontier_mode not in FRONTIER_MODES:
            raise ValueError(f"unknown frontier mode {frontier_mode!r}; "
                             f"one of {FRONTIER_MODES}")
        self.plan = plan
        self.cfg = plan.gnn_config(cfg)
        self.params = params
        self.mode = mode
        self.frontier_mode = frontier_mode
        self.graph = plan.graph
        self.n_layers = len(params)
        self.sample = plan.sample
        # global padded sample of the live graph: frontier expansion +
        # the centralized runtime read the same truncated edge set
        self._gnbr, self._gwts = self.graph.neighbor_sample(self.sample)
        self._halo_plan: HaloPlan | None = (
            build_halo_plan(plan.part) if plan.part is not None else None)
        # bucketed ragged layout: values move through the bucketed flat
        # gather; the dense _halo_plan above stays the billing source of
        # truth for the traffic accountant (DESIGN.md §12)
        self._bp = plan.bucketed
        if self._bp is not None:
            self._bind_bucketed_tables()
        self._new_send: np.ndarray | None = None  # send slots churn created
        self._acts: list | None = None            # [K, n_max, F_l] per level
        #                                 (bucketed: per level a LIST of
        #                                  per-bucket [K_b, n_cap, F_l])
        self.last_update: StreamingUpdate | None = None
        self.ticks = 0
        # (layer, table_rows, padded_rows) triples seen by the dirty-rows
        # recompute — each new triple is a fresh _rows_step specialization,
        # the telemetry recompile-estimate counter's unit (DESIGN.md §14)
        self._compiled_keys: set = set()

    # ---- layout helpers -------------------------------------------------

    def _bind_bucketed_tables(self) -> None:
        self._bhalo = build_bucketed_halo_plan(self._bp)
        self._bfidx = tuple(jnp.asarray(i) for i in self._bhalo.flat_src)
        self._bfmask = tuple(jnp.asarray(m) for m in self._bhalo.halo_mask)

    @property
    def _k(self) -> int:
        return self.plan.n_clusters

    def _to_local(self, gmask: np.ndarray) -> np.ndarray:
        """[N] global bool -> [K, n_max] owned-row bool."""
        if self.plan.part is None:
            return gmask[None].copy()
        part = self.plan.part
        return gmask[part.local_nodes] & part.local_mask

    def _owned_features(self):
        """[K, n_max, F0] level-0 table (semi: the tier-0 assembled region
        tables — same rows the spoke gather produces). Bucketed plans
        return the per-bucket list instead."""
        from repro.core.partition import gather_features
        if self._bp is not None:
            return list(gather_bucketed_features(self.graph, self._bp))
        if self.plan.part is None:
            return self.graph.features[None].astype(np.float32)
        return gather_features(self.graph, self.plan.part)

    # ---- full refresh ---------------------------------------------------

    def full_refresh(self) -> float:
        """(Re)compute every cached level from scratch; returns seconds.

        Caches are kept device-resident (jnp) so incremental ticks patch
        dirty rows in place instead of re-uploading whole tables."""
        with tel.span("engine.full_refresh"):
            return self._full_refresh_impl()

    def _full_refresh_impl(self) -> float:
        t0 = time.perf_counter()
        nbr, wts = self.plan.neighbors, self.plan.weights
        hp = self._halo_plan
        xt = None if hp is None else exchange_tables(hp, "allgather")
        if self._bp is not None:
            acts = [[jnp.asarray(f) for f in self._owned_features()]]
        else:
            acts = [jnp.asarray(self._owned_features())]
        for l, layer in enumerate(self.params):
            act = l < self.n_layers - 1 or self.cfg.final_activation
            if self._bp is not None:
                flat = _flat_rows(*acts[l])
                acts.append([
                    _bucket_layer(acts[l][b],
                                  _gather_halo(flat, self._bfidx[b],
                                               self._bfmask[b]),
                                  jnp.asarray(nbr[b]), jnp.asarray(wts[b]),
                                  layer, cfg=self.cfg, act=act)
                    for b in range(self._bp.n_buckets)])
                continue
            # a centralized plan reads no halo: zero halo rows
            halo = (acts[l][:, :0] if xt is None
                    else _emulated_exchange(acts[l], xt, "allgather", None))
            acts.append(_bucket_layer(acts[l], halo, jnp.asarray(nbr),
                                      jnp.asarray(wts), layer, cfg=self.cfg,
                                      act=act))
        jax.block_until_ready(acts[-1])
        self._acts = acts
        return time.perf_counter() - t0

    def _sync_plan_feats(self, dirty0_local: np.ndarray | None = None
                         ) -> None:
        """The engine mutates the shared ExecutionPlan in place; keep its
        ``feats`` tables consistent with the live graph so a later
        ``plan.make_forward`` (or a fresh server on the same plan) sees
        current features. ``dirty0_local`` patches only mutated rows; None
        rebuilds wholesale."""
        g, plan = self.graph, self.plan
        # feature-only commits never route through _rebuild_structure, so
        # the live graph must be re-bound here too — a consumer reading
        # plan.graph (e.g. a re-planner building a replacement plan from
        # it) would otherwise see cold-start features forever
        plan.graph = g
        if plan.part is None:
            plan.feats = g.features[None]                # view, O(1)
            return
        if plan.bucketed is not None and plan.setting != "semi":
            bp = plan.bucketed
            if dirty0_local is None:
                plan.feats = gather_bucketed_features(g, bp)
                return
            for c in range(self._k):
                rows = np.nonzero(dirty0_local[c])[0]
                if len(rows):
                    plan.feats[bp.bucket_of[c]][bp.index_in[c], rows] = \
                        g.features[plan.part.local_nodes[c][rows]]
            return
        if plan.setting == "semi":
            hier = plan.hier
            if dirty0_local is None:
                from repro.core.partition import gather_spoke_features
                plan.feats = gather_spoke_features(g, hier)
                return
            for r in range(self._k):
                rows = np.nonzero(dirty0_local[r])[0]
                if len(rows):
                    plan.feats[r, hier.gather_spoke[r, rows],
                               hier.gather_slot[r, rows]] = \
                        g.features[plan.part.local_nodes[r][rows]]
            return
        if dirty0_local is None:
            from repro.core.partition import gather_features
            plan.feats = gather_features(g, plan.part)
            return
        for c in range(self._k):
            rows = np.nonzero(dirty0_local[c])[0]
            if len(rows):
                plan.feats[c][rows] = \
                    g.features[plan.part.local_nodes[c][rows]]

    # ---- structural rebuild --------------------------------------------

    def _rebuild_structure(self) -> None:
        """Re-derive the plan's tables from the mutated graph, keeping the
        node->cluster assignment (owned rows stay put; halo/send tables and
        the global sample change)."""
        g = self.graph
        plan = self.plan
        self._gnbr, self._gwts = g.neighbor_sample(self.sample)
        plan.graph = g
        if plan.part is None:
            plan.neighbors = self._gnbr[None]
            plan.weights = self._gwts[None]
            return
        part = _from_assignment(g, plan.part.assignment, self._k,
                                sample=self.sample)
        old = self._halo_plan
        new = build_halo_plan(part)
        self._new_send = _new_send_slots(old, new)
        self._halo_plan = new
        plan.part = part
        if self._bp is not None:
            # re-bucket with the previous grouping and never-shrinking caps
            # (same assignment => same cluster sizes => same groups), so
            # the cached activations keep their shapes and only the
            # halo/neighbor tables retrace — and only when a cap grew
            bp = bucket_partition(part, g, self.sample, like=self._bp)
            nbrs, wtss = build_bucketed_subgraphs(g, bp)
            self._bp = bp
            plan.bucketed = bp
            self._bind_bucketed_tables()
            plan.sub = None
            plan.neighbors = nbrs
            plan.weights = wtss
        else:
            sub = build_local_subgraphs(g, part, self.sample)
            plan.sub = sub
            plan.neighbors = sub.neighbors
            plan.weights = sub.weights
        if plan.hier is not None:
            plan.hier = dataclasses.replace(plan.hier, region=part)

    # ---- incremental tick ----------------------------------------------

    def apply_delta(self, delta: GraphDelta) -> StreamingUpdate:
        """Commit a mutation buffer and refresh only the dirty frontier.

        The buffer is cleared on success. Requires a prior ``full_refresh``
        (the caches must exist before they can be patched).
        """
        if self._acts is None:
            raise RuntimeError("call full_refresh() before apply_delta()")
        t0 = time.perf_counter()
        res = apply_deltas(self.graph, delta)
        self.graph = res.graph
        if res.structure_dirty.any():
            self._rebuild_structure()
        update = self._refresh_dirty(res, t0)
        delta.clear()
        self.ticks += 1
        self.last_update = update
        return update

    def _refresh_dirty(self, res: DeltaResult, t0: float) -> StreamingUpdate:
        l_total = self.n_layers
        fr = expand_frontier(self._gnbr, self._gwts, res.feature_dirty,
                             res.structure_dirty, l_total,
                             mode=self.frontier_mode)
        if not self.cfg.numerics.ideal:
            # global DAC scale couples every row — subset recompute would
            # quantize against a stale max|Z| (DESIGN.md §9): degrade
            self._sync_plan_feats()
            secs = self.full_refresh()
            self._new_send = None
            return StreamingUpdate(fr, self._full_traffic(), secs, full=True)
        dirty_locals = np.stack([self._to_local(fr.masks[l])
                                 for l in range(l_total + 1)])
        self._note_frontier(fr, dirty_locals)
        # level 0: patch mutated feature rows into the cached input table
        # (and the shared plan's feats tables, which track the live graph)
        self._sync_plan_feats(dirty_locals[0])
        if dirty_locals[0].any():
            part = self.plan.part
            for c in range(self._k):
                rows = np.nonzero(dirty_locals[0][c])[0]
                if not len(rows):
                    continue
                padded = _pad_rows(rows, dirty_locals.shape[2])
                ids = padded if part is None else part.local_nodes[c][padded]
                vals = jnp.asarray(self.graph.features[ids])
                if self._bp is not None:
                    b, j = int(self._bp.bucket_of[c]), \
                        int(self._bp.index_in[c])
                    self._acts[0][b] = _scatter_rows(
                        self._acts[0][b], j, jnp.asarray(padded), vals)
                else:
                    self._acts[0] = _scatter_rows(
                        self._acts[0], c, jnp.asarray(padded), vals)
        if self._bp is not None:
            self._refresh_dirty_bucketed(dirty_locals, l_total)
        else:
            self._refresh_dirty_dense(dirty_locals, l_total)
        jax.block_until_ready(self._acts[-1])
        traffic = None
        if self._halo_plan is not None:
            traffic = measure_incremental(
                self.plan, self._halo_plan, dirty_locals, self.cfg,
                mode=self.mode, new_send=self._new_send)
        self._new_send = None
        return StreamingUpdate(fr, traffic, time.perf_counter() - t0,
                               full=False)

    def _note_frontier(self, fr: FrontierMasks,
                       dirty_locals: np.ndarray) -> None:
        """Dirty-fraction / cache-reuse accounting for one tick."""
        reg = tel.get_registry()
        if not reg.enabled:
            return
        recomputed = int(dirty_locals[1:].sum())
        owned = (int(self.plan.part.local_mask.sum())
                 if self.plan.part is not None else self.graph.n_nodes)
        reg.counter("streaming.rows_recomputed").inc(recomputed)
        reg.counter("streaming.rows_cached").inc(
            max(self.n_layers * owned - recomputed, 0))
        reg.gauge("streaming.dirty_fraction").set(
            float(fr.recompute_fraction()))

    def _note_compile(self, key: tuple) -> None:
        """Count first-seen (layer, table_rows, padded_rows) shape triples —
        each is one expected _rows_step JIT specialization."""
        if key not in self._compiled_keys:
            self._compiled_keys.add(key)
            tel.counter("streaming.recompile_estimate").inc()

    def _refresh_dirty_dense(self, dirty_locals: np.ndarray,
                             l_total: int) -> None:
        tracer = tel.get_tracer()
        nbr, wts = self.plan.neighbors, self.plan.weights
        n_max = dirty_locals.shape[2]
        for l in range(l_total):
            layer = self.params[l]
            act = l < l_total - 1 or self.cfg.final_activation
            d = dirty_locals[l + 1]
            if not d.any():
                continue
            hp = self._halo_plan
            for c in range(self._k):
                rows = np.nonzero(d[c])[0]
                if not len(rows):
                    continue
                padded = _pad_rows(rows, d.shape[1])
                sub_nbr, sub_wts = nbr[c][padded], wts[c][padded]
                table = self._acts[l][c]
                if hp is not None and (sub_nbr >= n_max).any():
                    # only pay the halo gather when a dirty row reads one
                    with tracer.span("halo.gather", layer=l, cluster=c):
                        halo = (self._acts[l][hp.src_cluster[c],
                                              hp.src_slot[c]]
                                * jnp.asarray(hp.halo_mask[c].astype(
                                    np.float32))[:, None])
                        table = jnp.concatenate([table, halo], axis=0)
                self._note_compile((l, int(table.shape[0]), len(padded)))
                with tracer.span("halo.mvm", layer=l, cluster=c,
                                 rows=len(rows)):
                    out = _rows_step(table, jnp.asarray(sub_nbr),
                                     jnp.asarray(sub_wts), layer, self.cfg,
                                     act)
                with tracer.span("cache.scatter", layer=l + 1, cluster=c):
                    self._acts[l + 1] = _scatter_rows(
                        self._acts[l + 1], c, jnp.asarray(padded), out)

    def _refresh_dirty_bucketed(self, dirty_locals: np.ndarray,
                                l_total: int) -> None:
        """Per-bucket dirty-row patch: same dirty-row indices as the dense
        layout (owned rows are the members prefix in both), halo values via
        the bucketed flat gather, caches patched with the donated scatter."""
        bp = self._bp
        tracer = tel.get_tracer()
        nbrs, wtss = self.plan.neighbors, self.plan.weights
        for l in range(l_total):
            layer = self.params[l]
            act = l < l_total - 1 or self.cfg.final_activation
            d = dirty_locals[l + 1]
            if not d.any():
                continue
            flat = None
            for c in range(self._k):
                rows = np.nonzero(d[c])[0]
                if not len(rows):
                    continue
                b, j = int(bp.bucket_of[c]), int(bp.index_in[c])
                padded = _pad_rows(rows, bp.n_caps[b])
                sub_nbr = nbrs[b][j][padded]
                sub_wts = wtss[b][j][padded]
                table = self._acts[l][b][j]
                if (sub_nbr >= bp.n_caps[b]).any():
                    # only pay the flat build + halo gather when a dirty
                    # row actually reads a halo slot this layer
                    with tracer.span("halo.gather", layer=l, bucket=b,
                                     cluster=c):
                        if flat is None:
                            flat = _flat_rows(*self._acts[l])
                        halo = _gather_halo(flat, self._bfidx[b][j],
                                            self._bfmask[b][j])
                        table = jnp.concatenate([table, halo], axis=0)
                self._note_compile((l, b, int(table.shape[0]), len(padded)))
                with tracer.span("halo.mvm", layer=l, bucket=b, cluster=c,
                                 rows=len(rows)):
                    out = _rows_step(table, jnp.asarray(sub_nbr),
                                     jnp.asarray(sub_wts), layer, self.cfg,
                                     act)
                with tracer.span("cache.scatter", layer=l + 1, bucket=b):
                    self._acts[l + 1][b] = _scatter_rows(
                        self._acts[l + 1][b], j, jnp.asarray(padded), out)

    def commit_full(self, delta: GraphDelta | None = None) -> StreamingUpdate:
        """Apply a buffer (optional) and rebuild every cache level — the
        full-refresh path param swaps, cold starts, and the bit-accurate
        degradation route through. Unlike ``apply_delta`` it needs no
        existing caches."""
        t0 = time.perf_counter()
        n = self.graph.n_nodes
        fd = np.zeros(n, bool)
        sd = np.zeros(n, bool)
        if delta is not None and len(delta):
            res = apply_deltas(self.graph, delta)
            self.graph = res.graph
            if res.structure_dirty.any():
                self._rebuild_structure()
            fd, sd = res.feature_dirty, res.structure_dirty
            delta.clear()
            self._sync_plan_feats()
        self.full_refresh()
        fr = expand_frontier(self._gnbr, self._gwts, fd, sd, self.n_layers,
                             mode=self.frontier_mode)
        self._new_send = None
        self.ticks += 1
        self.last_update = StreamingUpdate(
            fr, self._full_traffic(), time.perf_counter() - t0, full=True)
        return self.last_update

    def _full_traffic(self) -> StreamingTrafficReport | None:
        """Per-layer billing of a full refresh (the degraded path ships
        everything every layer)."""
        if self._halo_plan is None:
            return None
        part = self.plan.part
        all_dirty = np.stack([part.local_mask] * (self.n_layers + 1))
        return measure_incremental(self.plan, self._halo_plan, all_dirty,
                                   self.cfg, mode=self.mode, new_send=None)

    # ---- outputs --------------------------------------------------------

    def embeddings(self) -> np.ndarray:
        """[N, out_dim] current embeddings in global node order."""
        if self._acts is None:
            raise RuntimeError("call full_refresh() first")
        return self.plan.scatter(self._acts[-1])


def _new_send_slots(old: HaloPlan, new: HaloPlan) -> np.ndarray | None:
    """Bool mask over ``new``'s send table marking slots absent from
    ``old`` — rows an alltoall must ship after structural churn even when
    their source value is clean (the peer has never cached them)."""
    if old is None:
        return None
    base = np.int64(max(int(old.send_slot.max(initial=0)),
                        int(new.send_slot.max(initial=0))) + 1)

    def keys(plan: HaloPlan) -> np.ndarray:
        k = plan.send_slot.shape[0]
        c = np.arange(k, dtype=np.int64)[:, None, None]
        j = np.arange(k, dtype=np.int64)[None, :, None]
        return (c * k + j) * base + plan.send_slot

    have = keys(old)[old.send_mask]
    return new.send_mask & ~np.isin(keys(new), have)
