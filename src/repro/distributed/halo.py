"""Decentralized + two-tier semi-decentralized GNN runtimes.

One device per cluster (the paper's "edge device"). Each layer needs remote
neighbor features (the paper's bidirectional e_ij communication volume); two
exchange strategies are provided:

  * ``allgather`` — every device gathers all owned feature tables and selects
    its halo rows. Simple, bandwidth = K * n_max * F per device. This is the
    paper-faithful "broadcast within the cluster" behavior.
  * ``alltoall``  — each device sends only the rows its peers actually need
    (precomputed send lists). Traffic matches the true boundary volume e_ij —
    the beyond-paper optimization (see EXPERIMENTS.md §Perf-GNN).

Both strategies exist on both runtimes: the SPMD shard_map path (collectives
over the cluster mesh axis) and the mesh-free *emulated* path (the identical
dataflow as host-side gathers/transposes over the leading cluster axis — the
single-process oracle, and the fallback when clusters outnumber devices).

The **semi-decentralized** setting (paper §5, DESIGN.md §7) is a two-tier
exchange over a ``HierPartition``:

  * tier 0 — intra-region spoke->head gather: each region head assembles its
    region feature table from its member spokes' tables (device-local in
    SPMD, where a head and its spokes share a device; a real deployment
    moves ``sum(spoke rows) * F`` bytes over the access link, which the
    traffic accountant reports).
  * tier 1 — head<->head boundary halo per layer, identical machinery to the
    decentralized exchange but over the region-level partition.

The per-device layer honors ``cfg.backend``: the composed ``jnp``/``pallas``
paths run aggregation then the feature transform, ``fused`` runs both stages
in one ``fused_gnn_layer`` kernel launch with Z resident in VMEM (so the
decentralized and semi-decentralized settings get the same HBM-traffic win
as the centralized path — DESIGN.md §5).

Where the exchange tables live: the SPMD forwards (``SpmdForward``) take
them as arguments of the jitted program, each split over the mesh's
cluster axis, so every device holds only its own ``[h_max]`` / ``[K,
s_max]`` slices and the compiled module holds no copy of any of them. The
emulated forwards close over them as constants on their one device.

The bucketed host loops open ``halo.gather`` and ``halo.mvm`` spans per
layer and bucket (``halo.tier0_gather`` for the semi tier 0). They time
dispatch and never wait for the device; inside a JAX profile they land on
the host timeline beside the device trace (DESIGN.md §14). The SPMD
forward is one jitted call and opens no span inside; its collectives
appear in a profile's device trace under their HLO opcodes.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import telemetry as tel
from repro.core.partition import (BucketedPartition, HierPartition,
                                  Partition)
from repro.kernels.crossbar_mvm import crossbar_matmul_signed_ref
from repro.kernels.csr_aggregate import aggregate, csr_aggregate_ref
from repro.kernels.fused_layer import fused_gnn_layer

EXCHANGE_MODES = ("allgather", "alltoall")
OVERLAP_MODES = ("overlap", "serial")


@dataclasses.dataclass
class HaloPlan:
    """Static exchange plan derived from a Partition (numpy, host-side)."""
    src_cluster: np.ndarray    # [K, h_max] owner cluster of each halo row
    src_slot: np.ndarray       # [K, h_max] owner-local slot
    halo_mask: np.ndarray      # [K, h_max] bool
    send_slot: np.ndarray      # [K, K, s_max] rows device k sends to peer j
    send_mask: np.ndarray      # [K, K, s_max] bool
    recv_to_halo: np.ndarray   # [K, K, s_max] halo row filled by recv (or 0)
    recv_mask: np.ndarray      # [K, K, s_max] bool

    @property
    def s_max(self) -> int:
        return self.send_slot.shape[2]


def build_halo_plan(part: Partition) -> HaloPlan:
    from repro.core.partition import halo_exchange_tables
    src_c, src_s, mask = halo_exchange_tables(part)
    k, h_max = src_c.shape
    # send lists: sends[c][j] = local slots of c needed by j
    sends = [[[] for _ in range(k)] for _ in range(k)]
    recv_halo = [[[] for _ in range(k)] for _ in range(k)]
    for c in range(k):
        for h in range(h_max):
            if mask[c, h]:
                owner = int(src_c[c, h])
                sends[owner][c].append(int(src_s[c, h]))
                recv_halo[c][owner].append(h)
    s_max = max(max((len(s) for row in sends for s in row), default=0), 1)
    send_slot = np.zeros((k, k, s_max), np.int32)
    send_mask = np.zeros((k, k, s_max), bool)
    recv_to_halo = np.zeros((k, k, s_max), np.int32)
    recv_mask = np.zeros((k, k, s_max), bool)
    for c in range(k):
        for j in range(k):
            s = sends[c][j]
            send_slot[c, j, :len(s)] = s
            send_mask[c, j, :len(s)] = True
            r = recv_halo[c][j]
            recv_to_halo[c, j, :len(r)] = r
            recv_mask[c, j, :len(r)] = True
    return HaloPlan(src_c, src_s, mask, send_slot, send_mask,
                    recv_to_halo, recv_mask)


def _exchange_allgather(x_own, src_c, src_s, mask, axis):
    full = jax.lax.all_gather(x_own, axis)            # [K, n_max, F]
    halo = full[src_c, src_s]                         # [h_max, F]
    return halo * mask[:, None]


def _exchange_alltoall(x_own, send_slot, send_mask, recv_to_halo, recv_mask,
                       h_max, axis):
    # send[j] = rows this device owes peer j: [K, s_max, F]
    send = x_own[send_slot] * send_mask[..., None]
    recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0,
                              tiled=False)            # [K, s_max, F]
    halo = jnp.zeros((h_max, x_own.shape[-1]), x_own.dtype)
    flat_idx = recv_to_halo.reshape(-1)
    flat = (recv * recv_mask[..., None]).reshape(-1, x_own.shape[-1])
    # masked scatter: padding rows all target slot 0 with zero contribution
    return halo.at[flat_idx].add(flat * recv_mask.reshape(-1)[:, None])


def _layer_step(table, nbr, wts, layer, cfg, act: bool):
    """One GNN layer on a device-local feature table, backend-dispatched.
    Honors cfg.numerics on every backend (same contract as core.gnn)."""
    if cfg.backend == "fused":
        return fused_gnn_layer(table, nbr, wts, layer["w"], layer["b"],
                               cfg.numerics, relu=act, tuned=cfg.tuned)
    z = (csr_aggregate_ref(table, nbr, wts) if cfg.backend == "jnp"
         else aggregate(table, nbr, wts, backend=cfg.backend,
                        tuned=cfg.tuned))
    if cfg.numerics.ideal:
        x = jnp.dot(z, layer["w"], preferred_element_type=jnp.float32)
    else:
        x = crossbar_matmul_signed_ref(z, layer["w"], cfg.numerics)
    x = x + layer["b"]
    return jax.nn.relu(x) if act else x


# the tables each exchange mode reads
_MODE_TABLES = {"allgather": ("src_c", "src_s", "hmask"),
                "alltoall": ("send_slot", "send_mask", "recv_to_halo",
                             "recv_mask")}


def exchange_tables(plan: HaloPlan, mode: str) -> dict:
    """The tables the ``mode`` exchange reads, as numpy arrays with the
    cluster axis leading (masks as float32 multipliers)."""
    every = dict(src_c=plan.src_cluster, src_s=plan.src_slot,
                 hmask=plan.halo_mask.astype(np.float32),
                 send_slot=plan.send_slot,
                 send_mask=plan.send_mask.astype(np.float32),
                 recv_to_halo=plan.recv_to_halo,
                 recv_mask=plan.recv_mask.astype(np.float32))
    return {n: every[n] for n in _MODE_TABLES[mode]}


def _plan_consts(plan: HaloPlan, mode: str) -> dict:
    return jax.tree.map(jnp.asarray, exchange_tables(plan, mode))


@dataclasses.dataclass
class SpmdForward:
    """An SPMD forward whose exchange tables are arguments of its program.

    ``program(params, feats, nbr, wts, tables)`` is the jitted
    ``shard_map``; ``tables`` maps names to ``[K, ...]`` arrays that
    ``sharding`` splits over the mesh's cluster axis, so each device holds
    only its own slice and the compiled module embeds none of them.
    Called as ``fwd(params, feats, nbr, wts)`` it places the tables on
    first use and passes them on every call; ``lower`` needs only their
    shapes, so it also lowers for devices that are described, not
    attached."""
    program: object
    tables: dict
    sharding: NamedSharding
    _placed: bool = dataclasses.field(default=False, init=False, repr=False)

    def placed(self) -> dict:
        """The tables on the mesh (placed once)."""
        if not self._placed:
            self.tables = jax.device_put(self.tables, self.sharding)
            self._placed = True
        return self.tables

    def __call__(self, params, feats, nbr, wts):
        return self.program(params, feats, nbr, wts, self.placed())

    def lower(self, params, feats, nbr, wts):
        specs = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=self.sharding),
            self.tables)
        return self.program.lower(params, feats, nbr, wts, specs)


def _spmd_forward(mesh, device_fn, tables: dict, axis: str) -> SpmdForward:
    """Jit ``device_fn(params, feats, nbr, wts, tables)`` as a
    ``shard_map`` over ``axis``: params replicated, everything else split
    on its leading cluster axis."""
    shard = P(axis)
    fn = jax.shard_map(device_fn, mesh=mesh,
                       in_specs=(P(), shard, shard, shard, shard),
                       out_specs=shard, check_vma=False)
    return SpmdForward(jax.jit(fn), tables, NamedSharding(mesh, shard))


def _spmd_layers(params, x, nbr, wts, cfg, t, mode, h_max, axis):
    """Per-device layer loop shared by the decentralized and semi SPMD
    forwards. ``t``: per-device exchange tables (leading axis stripped)."""
    n_layers = len(params)
    for i, layer in enumerate(params):
        if mode == "allgather":
            halo = _exchange_allgather(x, t["src_c"], t["src_s"],
                                       t["hmask"], axis)
        else:
            halo = _exchange_alltoall(x, t["send_slot"], t["send_mask"],
                                      t["recv_to_halo"], t["recv_mask"],
                                      h_max, axis)
        table = jnp.concatenate([x, halo], axis=0)      # [n_max+h_max, F]
        act = i < n_layers - 1 or cfg.final_activation
        x = _layer_step(table, nbr, wts, layer, cfg, act)
    return x


def make_decentralized_forward(mesh, cfg, plan: HaloPlan, n_max: int,
                               mode: str = "alltoall", axis: str = "data"):
    """Build the SPMD decentralized GNN forward for a given mesh/plan.

    Inputs (sharded on the leading cluster axis over ``axis``):
      feats   [K, n_max, F_in]   owned node features
      nbr/wts [K, n_max, S]      device-local padded subgraph
    Returns an ``SpmdForward``: ``fwd(params, feats, nbr, wts)`` gives the
    [K, n_max, out_dim] embeddings of owned nodes, and ``fwd.program``
    takes the ``mode``'s exchange tables as a fifth argument.
    """
    assert mode in EXCHANGE_MODES, mode
    h_max = plan.src_cluster.shape[1]

    def device_fn(params, feats, nbr, wts, tables):
        t = {n: v[0] for n, v in tables.items()}
        x = _spmd_layers(params, feats[0], nbr[0], wts[0], cfg, t, mode,
                         h_max, axis)
        return x[None]

    return _spmd_forward(mesh, device_fn, exchange_tables(plan, mode), axis)


def _emulated_exchange(x, t, mode, h_max):
    """Host-side halo exchange across the leading cluster axis — the
    collective-free twin of ``_exchange_allgather``/``_exchange_alltoall``.

    ``allgather`` picks each halo row straight out of the stacked owned
    tables; ``alltoall`` routes through the same send/recv tables as the
    SPMD collective (send -> axis transpose -> masked scatter), so the
    emulated path exercises the exact tables the wire traffic is billed on.
    Both return identical halos ([K, h_max, F]).
    """
    if mode == "allgather":
        return x[t["src_c"], t["src_s"]] * t["hmask"][..., None]
    k = x.shape[0]
    dev = jnp.arange(k)[:, None, None]
    send = x[dev, t["send_slot"]] * t["send_mask"][..., None]  # [K,K,s_max,F]
    recv = jnp.swapaxes(send, 0, 1)           # recv[c, j] = send[j, c]
    halo = jnp.zeros((k, h_max, x.shape[-1]), x.dtype)
    return halo.at[dev, t["recv_to_halo"]].add(
        recv * t["recv_mask"][..., None])


def _emulated_layers(params, x, nbr, wts, cfg, t, mode, h_max):
    k = x.shape[0]
    n_layers = len(params)
    for i, layer in enumerate(params):
        halo = _emulated_exchange(x, t, mode, h_max)    # [K, h_max, F]
        table = jnp.concatenate([x, halo], axis=1)      # [K, n_max+h_max, F]
        act = i < n_layers - 1 or cfg.final_activation
        x = jnp.stack([
            _layer_step(table[c], nbr[c], wts[c], layer, cfg, act)
            for c in range(k)])
    return x


def make_emulated_forward(cfg, plan: HaloPlan, mode: str = "allgather"):
    """Mesh-free decentralized forward: the same per-cluster dataflow and
    halo exchange as ``make_decentralized_forward``, but with the exchange
    realized host-side across the leading cluster axis instead of as a
    collective (``_emulated_exchange`` — both ``allgather`` and
    ``alltoall`` route identically to the SPMD modes). Used when the
    cluster count exceeds the device count and as the single-process oracle
    for the SPMD path.

    feats/nbr/wts: [K, n_max, {F,S}]. Returns [K, n_max, out_dim].
    """
    assert mode in EXCHANGE_MODES, mode
    h_max = plan.src_cluster.shape[1]
    consts = _plan_consts(plan, mode)

    @jax.jit
    def forward(params, feats, nbr, wts):
        return _emulated_layers(params, feats, nbr, wts, cfg, consts, mode,
                                h_max)

    return forward


@dataclasses.dataclass
class TwoTierPlan:
    """Static two-tier semi-decentralized exchange plan (DESIGN.md §7).

    ``region`` drives the tier-1 head<->head halo; the gather tables drive
    the tier-0 spoke->head assembly of each region's feature table.
    """
    region: HaloPlan
    gather_spoke: np.ndarray   # [R, n_max] spoke owning each region row
    gather_slot: np.ndarray    # [R, n_max] slot in that spoke's table
    gather_mask: np.ndarray    # [R, n_max] bool (valid region rows)
    n_max: int

    @property
    def h_max(self) -> int:
        return self.region.src_cluster.shape[1]


def build_two_tier_plan(hier: HierPartition) -> TwoTierPlan:
    return TwoTierPlan(build_halo_plan(hier.region), hier.gather_spoke,
                       hier.gather_slot, hier.region.local_mask,
                       hier.region.n_max)


def _tier0_tables(plan: TwoTierPlan) -> dict:
    return dict(gspoke=plan.gather_spoke, gslot=plan.gather_slot,
                gmask=plan.gather_mask.astype(np.float32))


def make_semi_forward(mesh, cfg, plan: TwoTierPlan,
                      mode: str = "alltoall", axis: str = "data"):
    """SPMD two-tier semi-decentralized forward (one device per head).

    Inputs (sharded on the leading region axis over ``axis``):
      spoke_feats [R, P, m_max, F_in]  per-spoke feature tables
      nbr/wts     [R, n_max, S]        region-local padded subgraph
    Tier 0 assembles the head's region table from its co-located spokes
    (device-local gather — the access-link upload is billed by the traffic
    accountant, not moved over the mesh); tier 1 runs the per-layer
    head<->head halo exchange collective. Returns an ``SpmdForward``
    (``make_decentralized_forward``) giving [R, n_max, out_dim].
    """
    assert mode in EXCHANGE_MODES, mode
    h_max = plan.h_max

    def device_fn(params, spoke_feats, nbr, wts, tables):
        t = {n: v[0] for n, v in tables.items()}
        x = (spoke_feats[0][t["gspoke"], t["gslot"]]
             * t["gmask"][:, None])                     # tier 0: [n_max, F]
        x = _spmd_layers(params, x, nbr[0], wts[0], cfg, t, mode, h_max,
                         axis)
        return x[None]

    tables = dict(_tier0_tables(plan), **exchange_tables(plan.region, mode))
    return _spmd_forward(mesh, device_fn, tables, axis)


@dataclasses.dataclass
class BucketedHaloPlan:
    """Static exchange plan for the capacity-bucketed layout (DESIGN.md §12).

    The exchange is realized as ONE gather per destination bucket out of a
    *flat* table concatenating every bucket's owned rows
    (``cluster_offset[c] = bucket base + index_in[c] * n_cap``): ragged
    per-bucket shapes stay out of the gather indices, and each bucket's
    fetch is an independent launch the scheduler can overlap with another
    bucket's layer step. Wire-level billing stays on the dense partition's
    send/recv tables (``repro.distributed.traffic``) — this plan only moves
    values.
    """
    flat_src: tuple       # per bucket [K_b, h_cap] int32 into the flat table
    halo_mask: tuple      # per bucket [K_b, h_cap] float32
    n_caps: tuple
    h_caps: tuple
    flat_rows: int        # total rows of the concatenated owned table

    @property
    def n_buckets(self) -> int:
        return len(self.flat_src)


def build_bucketed_halo_plan(bpart: BucketedPartition) -> BucketedHaloPlan:
    from repro.core.partition import halo_exchange_tables
    part = bpart.part
    src_c, src_s, mask = halo_exchange_tables(part)
    offset = np.zeros(part.n_clusters, np.int64)
    base = 0
    for b, cl in enumerate(bpart.clusters):
        for j, c in enumerate(cl):
            offset[c] = base + j * bpart.n_caps[b]
        base += len(cl) * bpart.n_caps[b]
    hcount = mask.sum(axis=1)
    fsrc, fmask = [], []
    for b, cl in enumerate(bpart.clusters):
        hc = bpart.h_caps[b]
        fs = np.zeros((len(cl), hc), np.int32)
        fm = np.zeros((len(cl), hc), np.float32)
        for j, c in enumerate(cl):
            h = int(hcount[c])
            fs[j, :h] = offset[src_c[c, :h]] + src_s[c, :h]
            fm[j, :h] = 1.0
        fsrc.append(fs)
        fmask.append(fm)
    return BucketedHaloPlan(tuple(fsrc), tuple(fmask), bpart.n_caps,
                            bpart.h_caps, base)


@jax.jit
def _flat_rows(*xs):
    """Concatenate per-bucket owned tables [K_b, n_cap, F] into the flat
    [sum(K_b * n_cap), F] table the bucketed halo gathers index."""
    return jnp.concatenate([x.reshape(-1, x.shape[-1]) for x in xs], axis=0)


@jax.jit
def _gather_halo(flat, idx, mask):
    """One bucket's halo fetch: [.., h_cap, F] rows out of the flat table,
    padding rows masked to zero."""
    return flat[idx] * mask[..., None]


@partial(jax.jit, static_argnames=("cfg", "act"))
def _bucket_layer(x, halo, nbr, wts, w, b, *, cfg, act):
    """One GNN layer over one bucket [K_b, n_cap(+h_cap), ...].

    The halo buffer is freshly allocated per layer by ``_gather_halo`` and
    dead after the concat; it is not donated here because its shape never
    matches an output (XLA would warn and ignore it) — the donation that
    kills per-tick host round-trips lives on the streaming engine's
    same-shape activation-cache scatters (DESIGN.md §12). The owned table
    ``x`` is never donated — callers hold it across repeated calls."""
    layer = {"w": w, "b": b}
    table = jnp.concatenate([x, halo], axis=1)
    return jnp.stack([
        _layer_step(table[c], nbr[c], wts[c], layer, cfg, act)
        for c in range(x.shape[0])])


def make_emulated_bucketed_forward(cfg, bplan: BucketedHaloPlan,
                                   mode: str = "alltoall",
                                   overlap: str = "overlap"):
    """Mesh-free decentralized forward over the bucketed ragged layout.

    feats/nbr/wts: tuples of per-bucket [K_b, n_cap, {F, s_cap}] tables.
    Returns a tuple of per-bucket [K_b, n_cap, out_dim] arrays.

    ``mode`` is accepted for API symmetry with the dense runtimes: both
    exchange strategies produce identical halo *values*, and the bucketed
    plan realizes them with the same flat gather — the allgather/alltoall
    distinction lives in the traffic accountant's billing of the dense
    send/recv tables, not here. ``overlap="overlap"`` dispatches every
    bucket's halo gather before any bucket's layer step, so JAX's async
    dispatch overlaps the fetches (the comm stand-in) with the MVMs;
    ``"serial"`` interleaves fetch -> step per bucket. Same values either
    way (gate: overlapped tick <= serialized, benchmarks/scale_serve.py).
    """
    assert mode in EXCHANGE_MODES, mode
    assert overlap in OVERLAP_MODES, overlap
    fidx = tuple(jnp.asarray(i) for i in bplan.flat_src)
    fmask = tuple(jnp.asarray(m) for m in bplan.halo_mask)
    nb = bplan.n_buckets

    def forward(params, feats, nbrs, wtss):
        # Spans here time *dispatch* (the loop body runs ahead of the
        # device) and never wait for it, so the overlap schedule is the
        # same with telemetry on or off; the device's time per bucket is
        # in the device trace of a profile, on the spans' clock.
        tracer = tel.get_tracer()
        xs = list(feats)
        n_layers = len(params)
        for i, layer in enumerate(params):
            act = i < n_layers - 1 or cfg.final_activation
            flat = _flat_rows(*xs)
            if overlap == "overlap":
                halos = []
                for b in range(nb):
                    with tracer.span("halo.gather", layer=i, bucket=b):
                        halos.append(_gather_halo(flat, fidx[b], fmask[b]))
                xs_next = []
                for b in range(nb):
                    with tracer.span("halo.mvm", layer=i, bucket=b):
                        xs_next.append(
                            _bucket_layer(xs[b], halos[b], nbrs[b], wtss[b],
                                          layer["w"], layer["b"], cfg=cfg,
                                          act=act))
                xs = xs_next
            else:
                for b in range(nb):
                    with tracer.span("halo.gather", layer=i, bucket=b):
                        halo = _gather_halo(flat, fidx[b], fmask[b])
                    with tracer.span("halo.mvm", layer=i, bucket=b):
                        xs[b] = _bucket_layer(xs[b], halo, nbrs[b], wtss[b],
                                              layer["w"], layer["b"],
                                              cfg=cfg, act=act)
        return tuple(xs)

    return forward


_tier0_bucket_gather = jax.jit(
    lambda spoke, cids, gs, sl, gm:
    spoke[cids[:, None], gs, sl] * gm[..., None])


def make_emulated_bucketed_semi_forward(cfg, bplan: BucketedHaloPlan,
                                        hier: HierPartition,
                                        bpart: BucketedPartition,
                                        mode: str = "alltoall",
                                        overlap: str = "overlap"):
    """Two-tier semi forward over the bucketed layout: the tier-0
    spoke->head gather assembles each bucket's region tables straight from
    the (dense) spoke tables, then the bucketed tier-1 runtime takes over.

    spoke_feats: [R, P, m_max, F]; nbr/wts: per-bucket tuples.
    Returns a tuple of per-bucket [K_b, n_cap, out_dim] arrays.
    """
    t0 = []
    n_max = hier.region.n_max
    for b, cl in enumerate(bpart.clusters):
        ncap = bplan.n_caps[b]
        w = min(ncap, n_max)
        gs = np.zeros((len(cl), ncap), np.int32)
        sl = np.zeros((len(cl), ncap), np.int32)
        gm = np.zeros((len(cl), ncap), np.float32)
        gs[:, :w] = hier.gather_spoke[cl, :w]
        sl[:, :w] = hier.gather_slot[cl, :w]
        gm[:, :w] = hier.region.local_mask[cl, :w]
        t0.append(tuple(jnp.asarray(a) for a in
                        (cl.astype(np.int32), gs, sl, gm)))
    inner = make_emulated_bucketed_forward(cfg, bplan, mode=mode,
                                           overlap=overlap)

    def forward(params, spoke_feats, nbrs, wtss):
        with tel.get_tracer().span("halo.tier0_gather", buckets=len(t0)):
            feats = tuple(_tier0_bucket_gather(spoke_feats, cids, gs, sl, gm)
                          for cids, gs, sl, gm in t0)
        return inner(params, feats, nbrs, wtss)

    return forward


def make_emulated_semi_forward(cfg, plan: TwoTierPlan,
                               mode: str = "allgather"):
    """Mesh-free two-tier semi forward — the single-process oracle for
    ``make_semi_forward`` (same tier-0 gather tables, same tier-1 exchange
    via ``_emulated_exchange``).

    spoke_feats: [R, P, m_max, F]; nbr/wts: [R, n_max, S] region-local.
    Returns [R, n_max, out_dim].
    """
    assert mode in EXCHANGE_MODES, mode
    h_max = plan.h_max
    t0 = jax.tree.map(jnp.asarray, _tier0_tables(plan))
    consts = _plan_consts(plan.region, mode)

    @jax.jit
    def forward(params, spoke_feats, nbr, wts):
        r = spoke_feats.shape[0]
        x = (spoke_feats[jnp.arange(r)[:, None], t0["gspoke"], t0["gslot"]]
             * t0["gmask"][..., None])                  # tier 0: [R,n_max,F]
        return _emulated_layers(params, x, nbr, wts, cfg, consts, mode,
                                h_max)

    return forward
