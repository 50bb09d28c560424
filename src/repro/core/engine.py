"""Differential IFE engine — the paper's maintenance procedure, dense on TPU.

One engine serves every configuration in the paper:

* ``mode="vdc"``  — vanilla DC: the Join output ``J`` is materialized as a
  per-edge difference store (memory ∝ E, the paper's Table-1 bottleneck) and
  the aggregator reassembles messages *from that store*.
* ``mode="jod"``  — Join-On-Demand (§4): no J store; messages are recomputed
  from in-neighbour states on the fly (δE/δD direct rules + upper-bound rule
  realized as the dirty/frontier schedule below).
* ``drop.mode="det"|"prob"`` on top of JOD — partial dropping (§5) with
  deterministic or Bloom-filter DroppedVT and Random/Degree selection.

Timestamps are eager-merged (§4.2) so each (query, vertex) holds a 1-D sorted
list of (iteration, state) change points; negative multiplicities are implied
(DESIGN.md §2).

Maintenance is a bounded forward sweep over IFE iterations.  Per iteration i:

    cur        exact D_{i-1} for every vertex (repaired on the fly)
    sched_i    vertices whose aggregator must rerun: frontier (δD direct
               rule) ∪ dirty (δE direct rule + upper-bound rule: touched
               endpoints are rerun at every live iteration — spurious reruns
               are safe, Thm 4.1 corollary)
    repair_i   vertices whose change point at i was dropped → recompute to
               keep ``cur`` exact (AccessDᵢᵛWithDrops, forward form)
    changed_i  sched_i whose recomputed value differs from the pre-update
               trajectory → out-neighbours enter frontier_{i+1}

The sweep ends when the frontier is empty and i exceeds the stored horizon
(max change-point iteration), bounded by ``max_iters``.  Every step is pure
and fixed-shape → one ``lax.while_loop`` jits/lowers for the production mesh.

**Vertex-sharded sweep** (DESIGN.md §8): every per-vertex carry — diff-store
rows, DroppedVT/Bloom state, frontier/dirty masks, repair counts — partitions
by destination vertex over the mesh ``data`` axis (``maintain_sharded`` /
``batched_step_sharded`` run the same ``_sweep_body`` under ``shard_map``).
Cross-shard edges are handled by all-gathering the O(V) exact front ``cur``
once per iteration: messages are formed shard-locally against the gathered
row, so the COO segment-reduce and the ELL kernel both run unchanged on
their local partition, and the termination check becomes a ``psum``.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import bloom as bloom_lib
from repro.core import diffstore as ds
from repro.core import dropping as dr
from repro.core.graph import (
    DynamicGraph,
    EllIndex,
    EllOverflow,
    GraphSnapshot,
    ShardIndex,
    ShardOverflow,
)
from repro.core.semiring import Semiring, reduce_pair
from repro.kernels.ell_spmv import ell_spmv
from repro.kernels.interpret import resolve_interpret
from repro.obs import trace as obs_trace

# module (not name) import: kernels/fused_sweep.py imports repro.core for the
# diff-store/dropping primitives it runs in-kernel, so importing the *name*
# here would complete the cycle before the function exists
from repro.kernels import fused_sweep as fused_sweep_lib

Array = jnp.ndarray

# Mesh axis the sweep shards over (vertex partition).  The ``model`` axis is
# reserved for a future Q-axis model-parallel split.
DATA_AXIS = "data"


# --------------------------------------------------------------------------- graph arrays
class GraphArrays(NamedTuple):
    """Fixed-shape device view of the graph (COO + degrees).

    With ``backend="ell"`` the bucketed in-adjacency (``nbr``/``ell_w``,
    shape [V, D]) rides along for the Pallas SpMV; the COO arrays stay — the
    frontier push, the VDC join store and the δE dirty propagation are edge-
    indexed and keep using them.
    """

    src: Array  # int32 [E]
    dst: Array  # int32 [E]
    weight: Array  # f32 [E]
    valid: Array  # bool [E]
    out_degree: Array  # int32 [V]
    in_degree: Array  # int32 [V]
    nbr: Array | None = None  # int32 [V, D] in-neighbour ids (== V padding)
    ell_w: Array | None = None  # f32 [V, D] edge weights

    @property
    def num_vertices(self) -> int:
        return self.out_degree.shape[0]

    @property
    def ell_width(self) -> int:
        return 0 if self.nbr is None else int(self.nbr.shape[1])

    @classmethod
    def from_snapshot(
        cls, s: GraphSnapshot, *, backend: str = "coo", ell_min_width: int = 0
    ) -> "GraphArrays":
        nbr = ell_w = None
        if backend in ("ell", "fused"):
            nbr_np, w_np, _ = s.to_ell(min_width=ell_min_width)
            nbr, ell_w = jnp.asarray(nbr_np), jnp.asarray(w_np)
        return cls(
            src=jnp.asarray(s.src, jnp.int32),
            dst=jnp.asarray(s.dst, jnp.int32),
            weight=jnp.asarray(s.weight, jnp.float32),
            valid=jnp.asarray(s.valid),
            out_degree=jnp.asarray(s.out_degree, jnp.int32),
            in_degree=jnp.asarray(s.in_degree, jnp.int32),
            nbr=nbr,
            ell_w=ell_w,
        )


# --------------------------------------------------------------------------- config / state
@dataclasses.dataclass(frozen=True)
class EngineConfig:
    num_queries: int
    num_vertices: int
    max_iters: int
    semiring: Semiring
    mode: str = "jod"  # "vdc" | "jod"
    store_capacity: int = 16  # S: change points per (q, v)
    jstore_capacity: int = 8  # S_J: per-edge change points (vdc only)
    drop: dr.DropConfig = dataclasses.field(default_factory=dr.DropConfig)
    # PageRank: edge weight is alpha / outdeg(src), recomputed from degrees so
    # deletions retune every sibling message (dirty mask covers them).
    weight_from_degree: bool = False
    alpha: float = 0.85
    # Aggregator backend: "coo" = masked segment-reduce over the edge list;
    # "ell" = the Pallas bucketed-ELL SpMV kernel (JOD only — the kernel *is*
    # the fused Join+Min; interpret-mode fallback runs it off-TPU);
    # "fused" = the maintenance megakernel (kernels/fused_sweep.py): ONE
    # pallas_call per sweep iteration fuses expand + diff-store append +
    # DroppedVT probe/update (JOD fully in-kernel; VDC keeps its J-store
    # maintenance in XLA and fuses the per-vertex store phase).
    backend: str = "coo"
    ell_block_v: int = 128
    # None → interpret off-TPU, compiled Mosaic on TPU (kernels.ops default).
    interpret: bool | None = None

    def __post_init__(self):
        if self.mode not in ("vdc", "jod"):
            raise ValueError(f"unknown mode {self.mode!r}")
        if self.backend not in ("coo", "ell", "fused"):
            raise ValueError(f"unknown backend {self.backend!r}")
        if self.backend == "ell" and self.mode != "jod":
            raise ValueError("backend='ell' realizes JOD; VDC reads the J store")


class EngineState(NamedTuple):
    dstore: ds.DiffStore  # [Q, V, S] — the Iterate operator's difference store
    jstore: ds.DiffStore | None  # [Q, E, S_J] — the Join operator's store (vdc)
    drop: dr.DropState
    init: Array  # f32 [Q, V] — D_0 (implicit iteration-0 diffs)
    cur: Array  # f32 [Q, V] — exact values at the last swept iteration
    repair_counts: Array  # int32 [Q, V] — dropped-diff recomputations (Fig 6b)
    active: Array  # bool [Q] — live query slots; inactive slots are scheduled
    # for no work and hold no diffs (the session's padded slot pool)
    join_mat: Array | None = None  # bool [Q] — per-slot Join materialization
    # (vdc engines only): False = that slot's join differences are dropped
    # completely and its messages recompute on demand (JOD, §4) even though
    # the engine carries a J store for its neighbours


# Per-iteration probe depth: sweep iterations beyond this fold into the last
# bin.  Bounded so the stats pytree keeps a fixed shape inside the while_loop
# (one jit cache entry) and the host-side export stays O(1) per sweep.
ITER_TRACE = 32


class MaintainStats(NamedTuple):
    iters_run: Array  # int32
    scheduled: Array  # int32 — Σ|sched_i| (algorithmic work, vertex reruns)
    changed: Array  # int32 — Σ|changed_i| (δD differences produced)
    repairs: Array  # int32 — Σ|repair_i \ sched_i| (dropped diffs recomputed)
    written: Array  # int32 — change points upserted
    removed: Array  # int32 — change points deleted (cancelled +/- pairs)
    dropped: Array  # int32 — change points dropped instead of stored
    jwritten: Array  # int32 — J change points upserted (vdc)
    det_overflow: Array  # int32 — dropped VT records lost to Det-Drop store
    # evictions THIS sweep: each one is a (v, i) the engine can no longer
    # repair on access, so a nonzero value flags answers at risk of staleness
    active_edges: Array  # f32 — Σ_i Σ_q live in-edges of the sched|repair
    # rows (the edges a work-efficient sweep would read; float so 64
    # iterations of Q × 2^23 edges cannot overflow)
    sched_sizes: Array  # int32 [ITER_TRACE] — |sched_i| per iteration
    frontier_sizes: Array  # int32 [ITER_TRACE] — |frontier_{i+1}| per iteration

    SCALAR_FIELDS = (
        "iters_run", "scheduled", "changed", "repairs", "written",
        "removed", "dropped", "jwritten", "det_overflow", "active_edges",
    )
    VECTOR_FIELDS = ("sched_sizes", "frontier_sizes")


def zeros_stats() -> MaintainStats:
    z = jnp.zeros((), jnp.int32)
    t = jnp.zeros((ITER_TRACE,), jnp.int32)
    return MaintainStats(z, z, z, z, z, z, z, z, z, jnp.zeros((), jnp.float32), t, t)


# --------------------------------------------------------------------------- IFE primitives
def effective_weight(cfg: EngineConfig, g: GraphArrays) -> Array:
    if cfg.weight_from_degree:
        outd = jnp.maximum(g.out_degree[g.src], 1).astype(jnp.float32)
        return jnp.float32(cfg.alpha) / outd
    return g.weight


def edge_messages(cfg: EngineConfig, states: Array, g: GraphArrays) -> Array:
    """J from D: per-edge messages, identity on invalid slots. [Q, E]"""
    sr = cfg.semiring
    msgs = sr.msg(states[:, g.src], effective_weight(cfg, g)[None, :])
    return jnp.where(g.valid[None, :], msgs, sr.identity)


def aggregate(
    cfg: EngineConfig,
    msgs: Array,
    cur: Array,
    g: GraphArrays,
    *,
    dst: Array | None = None,
    num_segments: int | None = None,
) -> Array:
    """D_i from J_i (+ carry of D_{i-1}): the Min/Sum operator. [Q, V]

    The sharded sweep passes shard-local destination ids and segment count;
    out-of-range ids (foreign/padding edges) are dropped by the segment op.
    """
    sr = cfg.semiring
    dst = g.dst if dst is None else dst
    v = cfg.num_vertices if num_segments is None else num_segments
    if sr.reduce == "min":
        seg = jax.vmap(lambda m: jax.ops.segment_min(m, dst, num_segments=v))
    else:
        seg = jax.vmap(lambda m: jax.ops.segment_sum(m, dst, num_segments=v))
    agg = seg(msgs)
    if sr.carry_prev:
        return reduce_pair(sr, agg, cur)
    return jnp.float32(sr.base) + agg


def _ell_weights(cfg: EngineConfig, g: GraphArrays) -> Array:
    """ELL weight tile; degree-derived weights are re-gathered every step so
    a δE batch retunes every sibling message without rewriting [V, D] cells."""
    if cfg.weight_from_degree:
        outd = jnp.concatenate(
            [jnp.maximum(g.out_degree, 1), jnp.ones((1,), jnp.int32)]
        )  # index V (padding sentinel) → 1; its state is the identity 0 anyway
        return jnp.float32(cfg.alpha) / outd[g.nbr].astype(jnp.float32)
    return g.ell_w


def ell_step(
    cfg: EngineConfig, cur: Array, g: GraphArrays, *, carry: Array | None = None
) -> Array:
    """One exact IFE step through the Pallas bucketed-ELL SpMV (JOD fused).

    ``cur`` is the full state row the kernel gathers from; ``carry`` (default
    ``cur``) is the shard-local slice matching ``g.nbr``'s rows.
    """
    sr = cfg.semiring
    q = cur.shape[0]
    loc = cur if carry is None else carry
    states = jnp.concatenate(
        [cur, jnp.full((q, 1), sr.identity, cur.dtype)], axis=1
    )  # padding cells gather the reduce identity at the sentinel index
    kcarry = loc if sr.carry_prev else jnp.full_like(loc, sr.base)
    return ell_spmv(
        states,
        g.nbr,
        _ell_weights(cfg, g),
        kcarry,
        semiring=sr.kernel_name,
        block_v=cfg.ell_block_v,
        interpret=resolve_interpret(cfg.interpret),
        hop_cap=sr.hop_cap,
    )


def ife_step(
    cfg: EngineConfig,
    cur: Array,
    g: GraphArrays,
    *,
    carry: Array | None = None,
    dst: Array | None = None,
    num_segments: int | None = None,
) -> Array:
    """One exact IFE step D_{i-1} → D_i (join recomputed — the JOD path).

    ``cur`` is the full [Q, V] front; under the sharded sweep the optional
    ``carry``/``dst``/``num_segments`` restrict the output to the local
    vertex partition.
    """
    if cfg.backend in ("ell", "fused"):
        # the fused backend carries the same blocked-ELL adjacency; the
        # standalone expand (reassembly/repair paths) is bit-identical to
        # the megakernel's in-kernel tile (shared ``expand_tile``)
        return ell_step(cfg, cur, g, carry=carry)
    return aggregate(
        cfg,
        edge_messages(cfg, cur, g),
        cur if carry is None else carry,
        g,
        dst=dst,
        num_segments=num_segments,
    )


def push_frontier(
    changed: Array,
    g: GraphArrays,
    *,
    dst: Array | None = None,
    num_segments: int | None = None,
) -> Array:
    """Out-neighbour mask of changed vertices (δD direct rule).

    ``changed`` spans the full vertex axis (sources are global); the output
    covers ``num_segments`` destinations (the local partition when sharded).
    """
    dst = g.dst if dst is None else dst
    v = changed.shape[-1] if num_segments is None else num_segments
    hit = (changed[:, g.src] & g.valid[None, :]).astype(jnp.int32)
    out = jax.vmap(lambda h: jax.ops.segment_max(h, dst, num_segments=v))(hit)
    return out > 0


def _local_dst(dst: Array, off: Array, num_local: int) -> Array:
    """Map global destination ids to the local partition; foreign/padding
    ids collapse to ``num_local`` (out of range → dropped by segment ops)."""
    dl = dst - off
    return jnp.where((dl >= 0) & (dl < num_local), dl, num_local)


# --------------------------------------------------------------------------- maintenance
def make_state(
    cfg: EngineConfig,
    init: Array,
    num_edges: int,
    *,
    active: Array | None = None,
    drop_rows: list[dr.DropConfig] | None = None,
    join_rows: list[bool] | None = None,
) -> EngineState:
    """Engine state for ``cfg.num_queries`` slots.

    ``active`` marks the live slots (default: all); ``drop_rows`` supplies
    each slot's selection parameters (default: ``cfg.drop`` broadcast);
    ``join_rows`` each slot's Join materialization flag (vdc engines only;
    default: every slot materializes — the legacy uniform VDC).
    """
    q, v = cfg.num_queries, cfg.num_vertices
    assert init.shape == (q, v)
    jstore = (
        ds.make((q, num_edges), cfg.jstore_capacity) if cfg.mode == "vdc" else None
    )
    join_mat = None
    if jstore is not None:
        join_mat = (
            jnp.ones((q,), bool)
            if join_rows is None
            else jnp.asarray(join_rows, bool)
        )
    return EngineState(
        dstore=ds.make((q, v), cfg.store_capacity),
        jstore=jstore,
        drop=dr.make_state(cfg.drop, q, v, per_query=drop_rows),
        init=init.astype(jnp.float32),
        cur=init.astype(jnp.float32),
        repair_counts=jnp.zeros((q, v), jnp.int32),
        active=jnp.ones((q,), bool) if active is None else jnp.asarray(active, bool),
        join_mat=join_mat,
    )


def stored_horizon(store: ds.DiffStore) -> Array:
    """Max change-point iteration present anywhere (the upper-bound frontier)."""
    live = jnp.where(store.iters < ds.IMAX, store.iters, -1)
    return live.max()


class _Carry(NamedTuple):
    i: Array
    cur: Array  # exact D_{i-1} (local partition when sharded)
    cur_old: Array  # pre-update trajectory value at i-1 (store-lookup based)
    stale_old: Array  # bool [Q,V]: old trajectory obscured by a dropped diff
    frontier: Array  # bool [Q,V]: δD direct-rule schedule for iteration i
    changed_prev: Array  # bool [Q,V]: value changed at i-1 (feeds J updates;
    # sharded VDC carries it FULL-width — the gather from the previous
    # iteration's frontier push is reused instead of re-gathered)
    dstore: ds.DiffStore
    jstore: ds.DiffStore | None
    drop: dr.DropState
    repair_counts: Array
    horizon: Array  # int32 — running max change-point iteration (upper bound;
    # removals may leave it stale high, costing at most a few empty sweeps,
    # but avoids a full iters-store scan per iteration)
    live: Array  # bool — work remains (frontier ∪ dirty nonempty, globally);
    # precomputed in the body so the sharded cond stays collective-free
    stats: MaintainStats


def _sweep_body(
    cfg: EngineConfig,
    g: GraphArrays,
    dirty: Array,
    init: Array,
    old_dstore: ds.DiffStore,
    active: Array,
    join_mat: Array | None,
    axis: str | None,
    c: _Carry,
) -> _Carry:
    i = c.i
    num_local = c.cur.shape[-1]  # V, or V/n under shard_map
    q_ids = jnp.arange(cfg.num_queries, dtype=jnp.int32)[:, None]
    if axis is None:
        off = jnp.int32(0)
        cur_full = c.cur  # the exact front IS the full row
        dst = g.dst
        outd_local = g.out_degree
    else:
        off = jax.lax.axis_index(axis).astype(jnp.int32) * num_local
        # the one O(V) exchange per iteration: the exact front, gathered so
        # cross-shard edges form their messages against remote sources
        with jax.named_scope("cqp.expand"):
            cur_full = jax.lax.all_gather(c.cur, axis, axis=1, tiled=True)
        dst = _local_dst(g.dst, off, num_local)
        outd_local = jax.lax.dynamic_slice_in_dim(g.out_degree, off, num_local)
    v_ids = off + jnp.arange(num_local, dtype=jnp.int32)[None, :]
    degree = (outd_local + g.in_degree)[None, :].astype(jnp.float32)

    # -- δE direct + upper-bound rules: dirty endpoints rerun at every live i.
    #    ``dirty`` is per-query [Q, V]: a δE batch dirties every query's row,
    #    a mid-stream register dirties only the new slot's.  Inactive slots
    #    (the session's free pool) are scheduled for no work at all.
    with jax.named_scope("cqp.frontier"):
        sched = (c.frontier | dirty) & active[:, None]
    # live in-edges of the scheduled rows (MaintainStats.active_edges; the
    # repaired rows' are counted once, after the loop).  Formed from the
    # carried frontier behind a barrier: a reduction of `sched` itself fused
    # with the `cur` select and moved the frontier gather's [Q, V] table out
    # of the TPU's on-chip memory, 4% of a chunk (v5e, 589,824 vertices)
    sched_edges = jnp.where(
        (jax.lax.optimization_barrier(c.frontier) | dirty) & active[:, None],
        g.in_degree[None, :],
        0,
    ).sum(dtype=jnp.float32)

    # -- recompute D_i (dense; `sched|repair` is the algorithmic work mask).
    #    Each phase runs under a ``cqp.*`` name scope, which XLA keeps in
    #    every op's ``op_name`` metadata (DESIGN.md §15).
    if cfg.mode == "vdc":
        with jax.named_scope("cqp.expand"):
            # Maintain J at iteration i before reading it: an edge's message
            # changes when its source changed at i-1, or the edge itself (or a
            # sibling in-edge of its target) was touched by δE.  ``join_mat``
            # gates the store per slot: a slot whose Join differences are
            # dropped completely (§4) writes nothing and recomputes its
            # messages on demand — JOD inside a VDC engine.
            live_msgs = edge_messages(cfg, cur_full, g)
            jprev, _, jfound = ds.lookup_le(c.jstore, i)
            j0 = edge_messages(cfg, init, g)  # implicit J from D_0
            jprev = jnp.where(jfound, jprev, j0)
            # NOTE: deliberately NOT masked by g.valid — a deleted edge must
            # overwrite its stored message with the identity.
            dirty_pad = jnp.concatenate(
                [dirty, jnp.zeros((dirty.shape[0], 1), bool)], axis=1
            )
            jmat = join_mat[:, None]
            jdirty = c.changed_prev[:, g.src] | dirty_pad[:, dst]
            jwrite = jdirty & (live_msgs != jprev) & jmat
            jstore, _, _ = ds.upsert(c.jstore, i, jwrite, live_msgs)
            # VDC path: the aggregator *reads* the materialized J difference
            # sets for materializing slots, the on-demand messages otherwise.
            jval, _, jfound2 = ds.lookup_le(jstore, i)
            msgs = jnp.where(jmat, jnp.where(jfound2, jval, j0), live_msgs)
            new = aggregate(cfg, msgs, c.cur, g, dst=dst, num_segments=num_local)
            jwritten = c.stats.jwritten + jwrite.sum(dtype=jnp.int32)
    else:
        jstore = c.jstore
        jwritten = c.stats.jwritten
        # backend="fused" realizes JOD's expand *inside* the megakernel;
        # VDC hands the aggregated `new` to the kernel (partial fusion).
        new = None
        if cfg.backend != "fused":
            with jax.named_scope("cqp.expand"):
                new = ife_step(
                    cfg, cur_full, g, carry=c.cur, dst=dst, num_segments=num_local
                )

    if cfg.backend == "fused":
        # -- the maintenance megakernel: ONE pallas_call per iteration fuses
        #    frontier expand (JOD), DroppedVT/Bloom probe + repair masking,
        #    δ detection against the frozen old store, and the diff-store
        #    append/remove — intermediate tiles never leave VMEM.  The body
        #    calls the same library primitives as the stitched path below
        #    (expand_tile, ds.*, dr.select_to_drop, bloom.query), so results
        #    are bit-identical.
        with jax.named_scope("cqp.fused_sweep"):
            sr = cfg.semiring
            kw: dict = {}
            if new is None:
                nq = cur_full.shape[0]
                kw["states"] = jnp.concatenate(
                    [cur_full, jnp.full((nq, 1), sr.identity, cur_full.dtype)],
                    axis=1,
                )
                kw["nbr"] = g.nbr
                kw["w"] = _ell_weights(cfg, g)
                kw["kcarry"] = (
                    c.cur if sr.carry_prev else jnp.full_like(c.cur, sr.base)
                )
            else:
                kw["new"] = new
            if cfg.drop.enabled():
                kw["degree"] = degree
                kw["params"] = c.drop.params
                if cfg.drop.mode == "det":
                    kw["det"] = c.drop.det
                else:
                    kw["bloom_bits"] = c.drop.flt.bits
                    kw["bloom_hashes"] = c.drop.flt.num_hashes
            out = fused_sweep_lib.fused_sweep(
                i,
                off,
                sched,
                active,
                c.cur,
                c.cur_old,
                c.stale_old,
                c.dstore,
                old_dstore,
                semiring=sr.kernel_name,
                hop_cap=sr.hop_cap,
                block_v=cfg.ell_block_v,
                drop_mode=cfg.drop.mode if cfg.drop.enabled() else "none",
                interpret=resolve_interpret(cfg.interpret),
                **kw,
            )
        dstore = ds.DiffStore(out.d_iters, out.d_vals, out.d_count)
        cur_next = out.cur
        old_i, stale = out.old, out.stale
        changed, repair = out.changed, out.repair
        to_store, to_drop, vanish = out.to_store, out.to_drop, out.vanish
        with jax.named_scope("cqp.drop"):
            drop_state = c.drop
            if cfg.drop.enabled():
                if cfg.drop.mode == "det":
                    # DroppedVT was maintained in VMEM; adopt the kernel's rows
                    # and fold the per-tile overflow/horizon partials back into
                    # the replicated scalars (sum/max are associative).
                    drop_state = drop_state._replace(
                        det=ds.DiffStore(
                            out.det_iters, c.drop.det.vals, out.det_count
                        ),
                        det_overflow=c.drop.det_overflow
                        + out.det_overflow.sum(dtype=jnp.int32),
                        max_iter=jnp.maximum(
                            c.drop.max_iter, out.det_max_iter.max()
                        ),
                    )
                else:
                    # Bloom inserts stay outside the kernel (XLA scatter; the OR
                    # is idempotent so ordering is immaterial) — identical bits
                    # to the stitched register pair; unregister is a prob no-op.
                    drop_state = dr.register(drop_state, i, out.to_drop, v_offset=off)
                    drop_state = dr.register(
                        drop_state, out.evicted_iter, out.evicted, v_offset=off
                    )
    else:
        # -- dropped change points at i must be recomputed to keep `cur`
        #    exact (AccessDᵢᵛWithDrops, forward form).  Prob-Drop may
        #    false-positive here → spurious but safe recompute.
        with jax.named_scope("cqp.drop"):
            dropped_here = (
                dr.dropped_at(c.drop, i, num_local, v_offset=off)
                if cfg.drop.enabled()
                else jnp.zeros_like(sched)
            )
            repair = dropped_here & active[:, None] & ~sched

        with jax.named_scope("cqp.store"):
            # -- pre-update trajectory at i (δ detection), from the frozen
            #    store.
            old_has, old_val = ds.value_at(old_dstore, i)
            old_i = jnp.where(old_has, old_val, c.cur_old)
            # A dropped old change point leaves old_i stale until the next
            # stored old point re-anchors it; stale scheduled vertices
            # propagate conservatively.
            stale = (c.stale_old | dropped_here) & ~old_has

            changed = sched & ((new != old_i) | stale)

            # -- new trajectory change point at i?  (vs exact D_{i-1} = cur)
            want_point = sched & (new != c.cur)
            has_cur, cur_stored_val = ds.value_at(c.dstore, i)

        with jax.named_scope("cqp.drop"):
            if cfg.drop.enabled():
                to_drop = want_point & dr.select_to_drop(
                    c.drop.params, degree, q_ids, v_ids, i
                )
                to_store = want_point & ~to_drop
            else:
                to_drop = jnp.zeros_like(want_point)
                to_store = want_point

        with jax.named_scope("cqp.store"):
            dstore, evicted, evicted_iter = ds.upsert(c.dstore, i, to_store, new)
            # one fused removal pass (each full remove_at rewrites the store):
            #   · a dropped point at i that had a stored twin loses the twin
            #   · a vanished change point (+/- pair cancelled) is deleted
            vanish = sched & ~want_point & has_cur
            dstore = ds.remove_at(dstore, i, (to_drop & has_cur) | vanish)

        with jax.named_scope("cqp.drop"):
            drop_state = c.drop
            if cfg.drop.enabled():
                drop_state = dr.register(drop_state, i, to_drop, v_offset=off)
                drop_state = dr.register(
                    drop_state, evicted_iter, evicted, v_offset=off
                )
                # a dropped record is stale once the point is stored or
                # vanished
                drop_state = dr.unregister(drop_state, i, to_store | vanish)

        # -- advance the exact trajectory.
        with jax.named_scope("cqp.store"):
            cur_next = jnp.where(
                sched | repair, new, jnp.where(has_cur, cur_stored_val, c.cur)
            )

    if cfg.drop.enabled() and axis is not None:
        # per-shard inserts merge back into the shared structures: OR the
        # Bloom bits (psum of bools), pmax the horizon anchor, psum the
        # overflow delta — all scalars/filters stay replicated.
        with jax.named_scope("cqp.drop"):
            if drop_state.flt is not None:
                bits = jax.lax.psum(drop_state.flt.bits.astype(jnp.int32), axis) > 0
                drop_state = drop_state._replace(flt=drop_state.flt._replace(bits))
            drop_state = drop_state._replace(
                det_overflow=c.drop.det_overflow
                + jax.lax.psum(drop_state.det_overflow - c.drop.det_overflow, axis),
                max_iter=jax.lax.pmax(drop_state.max_iter, axis),
            )
    with jax.named_scope("cqp.frontier"):
        changed_full = (
            changed
            if axis is None
            else jax.lax.all_gather(changed, axis, axis=1, tiled=True)
        )
        frontier_next = (
            push_frontier(changed_full, g, dst=dst, num_segments=num_local) | changed
        )  # | changed: carry a changed vertex's own next value

    # per-iteration probe: iteration i lands in bin i-1 (clamped to the last
    # bin) so short sweeps read directly as a size-per-iteration series
    bin_i = jnp.minimum(i - 1, jnp.int32(ITER_TRACE - 1))
    stats = MaintainStats(
        iters_run=c.stats.iters_run + 1,
        scheduled=c.stats.scheduled + sched.sum(dtype=jnp.int32),
        changed=c.stats.changed + changed.sum(dtype=jnp.int32),
        repairs=c.stats.repairs + repair.sum(dtype=jnp.int32),
        written=c.stats.written + to_store.sum(dtype=jnp.int32),
        removed=c.stats.removed + vanish.sum(dtype=jnp.int32),
        dropped=c.stats.dropped + to_drop.sum(dtype=jnp.int32),
        jwritten=jwritten,
        det_overflow=c.stats.det_overflow,  # folded in after the loop
        active_edges=c.stats.active_edges + sched_edges,
        sched_sizes=c.stats.sched_sizes.at[bin_i].add(
            sched.sum(dtype=jnp.int32)
        ),
        frontier_sizes=c.stats.frontier_sizes.at[bin_i].add(
            frontier_next.sum(dtype=jnp.int32)
        ),
    )
    any_store = to_store.any()
    live_next = frontier_next.any() | dirty.any()
    if axis is not None:
        any_store = jax.lax.psum(any_store.astype(jnp.int32), axis) > 0
        live_next = jax.lax.psum(live_next.astype(jnp.int32), axis) > 0
    horizon = jnp.where(any_store, jnp.maximum(c.horizon, i), c.horizon)
    return _Carry(
        i=i + 1,
        cur=cur_next,
        cur_old=old_i,
        stale_old=stale,
        frontier=frontier_next,
        # sharded VDC reuses this iteration's gathered mask next iteration
        changed_prev=changed_full if cfg.mode == "vdc" else changed,
        dstore=dstore,
        jstore=jstore,
        drop=drop_state,
        repair_counts=c.repair_counts + repair.astype(jnp.int32),
        horizon=horizon,
        live=live_next,
        stats=stats,
    )


def _maintain_core(
    cfg: EngineConfig,
    state: EngineState,
    g: GraphArrays,
    dirty: Array,
    *,
    axis: str | None = None,
) -> tuple[EngineState, MaintainStats]:
    """The maintenance while_loop, shared by the single-device path
    (``axis=None``) and the per-shard body under ``shard_map``.

    ``dirty`` is the per-query [Q, V] schedule seed (local vertex partition
    when sharded).  In sharded mode every per-vertex argument arrives as its
    local partition; loop-control scalars (``live``, ``horizon``,
    ``drop.max_iter``) are kept replicated by collectives in the body, so
    ``cond`` itself runs no communication and all shards take identical trip
    counts.
    """
    old_dstore = state.dstore  # frozen pre-maintenance snapshot (functional)
    if axis is None:
        init_full = state.init
        live0 = dirty.any()
        horizon0 = stored_horizon(state.dstore)
    else:
        init_full = jax.lax.all_gather(state.init, axis, axis=1, tiled=True)
        live0 = jax.lax.psum(dirty.any().astype(jnp.int32), axis) > 0
        horizon0 = jax.lax.pmax(stored_horizon(state.dstore), axis)

    body = partial(
        _sweep_body,
        cfg,
        g,
        dirty,
        init_full,
        old_dstore,
        state.active,
        state.join_mat,
        axis,
    )

    def cond(c: _Carry) -> Array:
        # Continue while work is scheduled (frontier/dirty) AND the sweep can
        # still mutate the store.  Mutations happen only at i ≤ horizon+1:
        # an in-neighbour change point at j feeds a consumer at j+1 (upper
        # bound rule), and fresh writes at i extend the horizon to ≥ i, so a
        # still-converging new trajectory keeps the loop alive while a
        # permanently-diverged-from-old frontier (no mutations) drains at
        # horizon+1 instead of max_iters.  i==1 always runs when anything is
        # dirty (δE direct rule).  The horizon rides the carry (one store
        # scan per maintain, not per iteration).
        horizon = c.horizon
        if cfg.drop.enabled():
            # dropped change points still anchor the upper-bound rule (and
            # must be swept past so `cur` picks up their repaired values)
            horizon = jnp.maximum(horizon, c.drop.max_iter)
        return (
            (c.i <= jnp.int32(cfg.max_iters))
            & c.live
            & ((c.i == 1) | (c.i <= horizon + 1))
        )

    num_local = state.cur.shape[-1]
    zeros = jnp.zeros((cfg.num_queries, num_local), bool)
    c0 = _Carry(
        i=jnp.int32(1),
        cur=state.init,
        cur_old=state.init,
        stale_old=zeros,
        frontier=zeros,
        changed_prev=(
            jnp.zeros((cfg.num_queries, cfg.num_vertices), bool)
            if cfg.mode == "vdc"
            else zeros
        ),
        dstore=state.dstore,
        jstore=state.jstore,
        drop=state.drop,
        repair_counts=state.repair_counts,
        horizon=horizon0,
        live=live0,
        stats=zeros_stats(),
    )
    c = jax.lax.while_loop(cond, body, c0)
    # the repaired rows' live in-edges, from how often the sweep repaired
    # each row: one pass here, not one an iteration
    repaired = (c.repair_counts - state.repair_counts).astype(jnp.float32)
    stats = c.stats._replace(
        active_edges=c.stats.active_edges
        + (repaired * g.in_degree[None, :]).sum()
    )
    if axis is not None:
        # per-shard partial sums → global; iters_run is already replicated
        stats = stats._replace(
            scheduled=jax.lax.psum(stats.scheduled, axis),
            changed=jax.lax.psum(stats.changed, axis),
            repairs=jax.lax.psum(stats.repairs, axis),
            written=jax.lax.psum(stats.written, axis),
            removed=jax.lax.psum(stats.removed, axis),
            dropped=jax.lax.psum(stats.dropped, axis),
            active_edges=jax.lax.psum(stats.active_edges, axis),
            jwritten=jax.lax.psum(stats.jwritten, axis),
            sched_sizes=jax.lax.psum(stats.sched_sizes, axis),
            frontier_sizes=jax.lax.psum(stats.frontier_sizes, axis),
        )
    # Det-Drop record loss this sweep (replicated in sharded mode: the body
    # psums the per-shard eviction deltas into the carried counter).
    stats = stats._replace(
        det_overflow=c.drop.det_overflow - state.drop.det_overflow
    )
    new_state = EngineState(
        dstore=c.dstore,
        jstore=c.jstore,
        drop=c.drop,
        init=state.init,
        cur=c.cur,
        repair_counts=c.repair_counts,
        active=state.active,
        join_mat=state.join_mat,
    )
    return new_state, stats


def _dirty_2d(cfg: EngineConfig, dirty: Array) -> Array:
    """Normalize a [V] vertex mask to the per-query [Q, V] schedule seed."""
    dirty = jnp.asarray(dirty, bool)
    if dirty.ndim == 1:
        dirty = jnp.broadcast_to(dirty[None, :], (cfg.num_queries, dirty.shape[0]))
    return dirty


def maintain(
    cfg: EngineConfig,
    state: EngineState,
    g: GraphArrays,
    dirty: Array,
) -> tuple[EngineState, MaintainStats]:
    """One maintenance sweep after a δE batch (or initial computation).

    ``dirty`` is the bool mask of vertices whose in-edge set (or, for
    degree-derived weights, whose incoming message weights) changed — [V]
    (broadcast to every query, the δE case) or [Q, V] (per-query: a
    mid-stream ``register`` seeds only the new slot's row, which makes the
    sweep the new query's initial computation while every other query is
    scheduled for zero work).  For the initial computation pass
    ``dirty = ones`` with an empty store — the sweep then *is* the static
    IFE run, recording change points as it goes.
    """
    return _maintain_core(cfg, state, g, _dirty_2d(cfg, dirty), axis=None)


# --------------------------------------------------------------------------- sharded sweep
def _store_pspec() -> ds.DiffStore:
    """Partition spec for a [Q, K, S] diff store: keys sharded, rest whole."""
    return ds.DiffStore(
        iters=P(None, DATA_AXIS, None),
        vals=P(None, DATA_AXIS, None),
        count=P(None, DATA_AXIS),
    )


def _state_pspecs(state: EngineState) -> EngineState:
    """EngineState partition specs: every per-vertex (and, for VDC, per-edge-
    cell) axis shards over ``data``; scalars and Bloom bits stay replicated."""
    drop = state.drop
    return EngineState(
        dstore=_store_pspec(),
        jstore=None if state.jstore is None else _store_pspec(),
        drop=dr.DropState(
            det=None if drop.det is None else _store_pspec(),
            flt=None
            if drop.flt is None
            else bloom_lib.BloomFilter(P(), drop.flt.num_hashes),
            det_overflow=P(),
            max_iter=P(),
            # per-query selection rows replicate (the Q axis never shards)
            params=None
            if drop.params is None
            else dr.DropParams(*([P()] * len(dr.DropParams._fields))),
        ),
        init=P(None, DATA_AXIS),
        cur=P(None, DATA_AXIS),
        repair_counts=P(None, DATA_AXIS),
        active=P(),
        join_mat=None if state.join_mat is None else P(),
    )


def _graph_pspecs(g: GraphArrays) -> GraphArrays:
    """GraphArrays partition specs for the vertex-sharded edge layout:
    edge cells and in-rows shard by destination; out-degrees replicate
    (message weights gather them at arbitrary global sources)."""
    return GraphArrays(
        src=P(DATA_AXIS),
        dst=P(DATA_AXIS),
        weight=P(DATA_AXIS),
        valid=P(DATA_AXIS),
        out_degree=P(),
        in_degree=P(DATA_AXIS),
        nbr=None if g.nbr is None else P(DATA_AXIS, None),
        ell_w=None if g.ell_w is None else P(DATA_AXIS, None),
    )


def _stats_pspecs() -> MaintainStats:
    return MaintainStats(*([P()] * len(MaintainStats._fields)))


def maintain_sharded(
    cfg: EngineConfig,
    mesh: Mesh,
    state: EngineState,
    g: GraphArrays,
    dirty: Array,
) -> tuple[EngineState, MaintainStats]:
    """``maintain`` with every per-vertex carry partitioned over the mesh
    ``data`` axis.  ``g`` must be in the :class:`ShardIndex` edge layout
    (cells grouped by destination shard) and V divisible by the axis size."""
    sspec = _state_pspecs(state)
    fn = jax.shard_map(
        partial(_maintain_core, cfg, axis=DATA_AXIS),
        mesh=mesh,
        in_specs=(sspec, _graph_pspecs(g), P(None, DATA_AXIS)),
        out_specs=(sspec, _stats_pspecs()),
        check_vma=False,
    )
    return fn(state, g, _dirty_2d(cfg, dirty))


def _batched_core_sharded(
    cfg: EngineConfig,
    state: EngineState,
    g: GraphArrays,
    upd: UpdateBatch,
    *,
    axis: str,
) -> tuple[EngineState, GraphArrays, MaintainStats]:
    """Per-shard body of the donated-buffer batched step: the (replicated)
    UpdateBatch is scattered to the owning shards — each shard localizes the
    chunk's indices and drops the rows it does not own — then the sharded
    sweep runs in the same dispatch."""
    es = g.src.shape[0]  # edge cells per shard
    num_local = state.cur.shape[-1]  # vertices per shard
    v = cfg.num_vertices
    shard = jax.lax.axis_index(axis).astype(jnp.int32)
    off = shard * num_local

    with jax.named_scope("cqp.edge_scatter"):
        # edge-cell scatter: upd.slot is the linear ShardIndex cell (shard·C + pos)
        slot = upd.slot - shard * es
        slot = jnp.where((slot >= 0) & (slot < es), slot, es)  # foreign → dropped
        src = g.src.at[slot].set(upd.src, mode="drop")
        dst = g.dst.at[slot].set(upd.dst, mode="drop")
        weight = g.weight.at[slot].set(upd.weight, mode="drop")
        valid = g.valid.at[slot].set(upd.valid, mode="drop")

        # degrees recomputed from the (distributed) edge list: out-degrees need a
        # cross-shard psum (any shard may hold out-edges of any source); in-
        # degrees are a shard-local property of the owned destination block.
        live = valid.astype(jnp.int32)
        out_degree = jax.lax.psum(
            jax.ops.segment_sum(live, src, num_segments=v), axis
        )
        dst_l = _local_dst(dst, off, num_local)
        in_degree = jax.ops.segment_sum(live, dst_l, num_segments=num_local)

        nbr, ell_w = g.nbr, g.ell_w
        if cfg.backend in ("ell", "fused"):
            row = upd.ell_row - off
            row = jnp.where((row >= 0) & (row < num_local), row, num_local)
            nbr = nbr.at[row, upd.ell_col].set(upd.ell_nbr, mode="drop")
            ell_w = ell_w.at[row, upd.ell_col].set(upd.ell_w, mode="drop")
        g2 = GraphArrays(src, dst, weight, valid, out_degree, in_degree, nbr, ell_w)

        dv = upd.dirty_v - off
        dv = jnp.where((dv >= 0) & (dv < num_local), dv, num_local)
        dirty = jnp.zeros(num_local + 1, bool).at[dv].set(True)[:num_local]
        if cfg.weight_from_degree:
            # outdeg(u) changed → every out-message of u retunes (δE dirty rule)
            tsrc = jnp.zeros(v + 1, bool).at[upd.touched_src].set(True)[:v]
            hit = (tsrc[src] & valid).astype(jnp.int32)
            dirty = dirty | (
                jax.ops.segment_max(hit, dst_l, num_segments=num_local) > 0
            )
        dirty = jnp.broadcast_to(dirty[None, :], (cfg.num_queries, num_local))

    new_state, stats = _maintain_core(cfg, state, g2, dirty, axis=axis)
    return new_state, g2, stats


def batched_step_sharded(
    cfg: EngineConfig,
    mesh: Mesh,
    state: EngineState,
    g: GraphArrays,
    upd: UpdateBatch,
) -> tuple[EngineState, GraphArrays, MaintainStats]:
    """Sharded twin of :func:`batched_step`: one dispatch scatters a δE chunk
    to the owning shards and runs the vertex-sharded maintenance sweep."""
    sspec, gspec = _state_pspecs(state), _graph_pspecs(g)
    fn = jax.shard_map(
        partial(_batched_core_sharded, cfg, axis=DATA_AXIS),
        mesh=mesh,
        in_specs=(sspec, gspec, UpdateBatch(*([P()] * len(UpdateBatch._fields)))),
        out_specs=(sspec, gspec, _stats_pspecs()),
        check_vma=False,
    )
    return fn(state, g, upd)


def shed_slot(
    cfg: EngineConfig, state: EngineState, g: GraphArrays, slot: Array | int
) -> EngineState:
    """Re-audit ONE query slot's stored diffs under its (just-rewritten)
    selection params: points the escalated policy selects move from the diff
    store into the DroppedVT structures (8 B change point → ≤4 B record, or
    Bloom bits), exactly as if they had been dropped at write time.

    This is the governor's reclamation primitive: raising a query's drop
    probability only thins FUTURE writes; ``shed_slot`` makes the escalation
    retroactive so memory falls immediately.  Correctness is the existing §5
    machinery — the sweep repairs dropped points on access — and because the
    selection coin is the stateless (seed, q, v, i) hash, a shed is
    bit-identical under any sharding.  ``cur`` (the answers) is untouched.
    """
    drop = state.drop
    degree = (g.out_degree + g.in_degree).astype(jnp.float32)
    sel = dr.select_stored_to_drop(
        drop.params, degree, state.dstore.iters, ds.IMAX
    )
    qmask = (
        jnp.arange(cfg.num_queries, dtype=jnp.int32) == jnp.asarray(slot)
    )[:, None, None]
    mask = sel & qmask & state.active[:, None, None]

    # record the shed points as dropped VTs, one store column per step —
    # dr.register takes per-(q, v) iteration arrays, and the Det-Drop store
    # is keyed by (q, v) so multiple iterations of one vertex cannot land in
    # a single upsert.  A traced fori_loop keeps the compiled program size
    # independent of the store capacity S (which regrows geometrically).
    def register_col(col, d):
        i_col = jax.lax.dynamic_index_in_dim(
            state.dstore.iters, col, axis=-1, keepdims=False
        )
        m_col = jax.lax.dynamic_index_in_dim(mask, col, axis=-1, keepdims=False)
        return dr.register(d, i_col, m_col)

    drop = jax.lax.fori_loop(0, state.dstore.capacity, register_col, drop)
    # remove them from the store, preserving the sorted-row invariant
    it = jnp.where(mask, ds.IMAX, state.dstore.iters)
    val = jnp.where(mask, 0.0, state.dstore.vals)
    order = jnp.argsort(it, axis=-1, stable=True)
    it = jnp.take_along_axis(it, order, axis=-1)
    val = jnp.take_along_axis(val, order, axis=-1)
    dstore = ds.DiffStore(
        iters=it, vals=val, count=(it < ds.IMAX).sum(axis=-1, dtype=jnp.int32)
    )
    return state._replace(dstore=dstore, drop=drop)


def reassemble(
    cfg: EngineConfig, state: EngineState, g: GraphArrays, upto: int | None = None
) -> Array:
    """Repair-aware reassembly of D at iteration ``upto`` (paper's Access).

    Bounded forward repair: walk iterations 1..upto; stored points are exact,
    dropped points are recomputed from the exact previous front.  Cost is
    O(upto × E) dense, but only dropped lanes represent algorithmic work.
    """
    upto = cfg.max_iters if upto is None else upto

    def body(i, cur):
        has, val = ds.value_at(state.dstore, i)
        if cfg.drop.enabled():
            dropped = dr.dropped_at(state.drop, i, cfg.num_vertices)
            new = ife_step(cfg, cur, g)
            return jnp.where(has, val, jnp.where(dropped, new, cur))
        return jnp.where(has, val, cur)

    return jax.lax.fori_loop(1, upto + 1, body, state.init)


def answers(cfg: EngineConfig, state: EngineState) -> Array:
    """Final vertex states after the last maintenance sweep. [Q, V]"""
    return state.cur


# --------------------------------------------------------------------------- memory accounting
def nbytes_accounted(cfg: EngineConfig, state: EngineState) -> int:
    """Difference-entry bytes, the paper's memory metric (8 B per diff:
    4 B iteration + 4 B state; DroppedVT per §5.1 costings, including the
    per-query selection rows and Bloom rows of LIVE slots only — a retired
    slot's zeroed rows are reclaimable and charge nothing)."""
    total = int(state.dstore.count.sum()) * 8
    if state.jstore is not None:
        total += int(state.jstore.count.sum()) * 8
    if cfg.drop.enabled():
        total += int(state.drop.nbytes_accounted(state.active))
    return total


def nbytes_per_shard(
    cfg: EngineConfig, state: EngineState, num_shards: int
) -> list[int]:
    """Accounted difference bytes resident on each shard of the vertex
    partition (the paper's Table-1 per-machine memory axis): diff-store and
    DroppedVT rows live with their owning vertex block, VDC's J rows with
    their owning edge-cell block.  Bloom bits and DropParams rows are
    *replicated* device-side, but accounted ONCE and apportioned evenly
    across the shards, so ``sum(nbytes_per_shard(...)) == nbytes_accounted``
    in every drop mode (the remainder lands on shard 0)."""
    q = cfg.num_queries
    per = (
        np.asarray(state.dstore.count).reshape(q, num_shards, -1).sum(axis=(0, 2))
        * 8
    )
    if state.jstore is not None:
        per = per + (
            np.asarray(state.jstore.count)
            .reshape(q, num_shards, -1)
            .sum(axis=(0, 2))
            * 8
        )
    if cfg.drop.enabled():
        if state.drop.det is not None:
            per = per + (
                np.asarray(state.drop.det.count)
                .reshape(q, num_shards, -1)
                .sum(axis=(0, 2))
                * 4
            )
            replicated = int(state.drop.nbytes_accounted(state.active)) - int(
                state.drop.det.count.sum() * 4
            )
        else:
            replicated = int(state.drop.nbytes_accounted(state.active))
        per = per + replicated // num_shards
        per[0] += replicated - (replicated // num_shards) * num_shards
    return [int(x) for x in per]


# --------------------------------------------------------------------------- batched updates
class UpdateBatch(NamedTuple):
    """Fixed-shape device encoding of ≤ B resolved edge updates.

    One row per touched edge slot, holding the slot's *final* contents after
    the whole chunk (the host coalesces, so duplicate-index scatter order
    never matters).  Padding rows carry out-of-range indices — slot == E_cap,
    vertex == V, ell_row == V — and are dropped by the scatters / sliced off
    the dirty mask.  The shape ``[B]`` is the jit cache key: every chunk of a
    long update log reuses one compiled program.
    """

    slot: Array  # int32 [B] — edge slot; E_cap padding
    src: Array  # int32 [B] — final slot source
    dst: Array  # int32 [B] — final slot destination
    weight: Array  # f32  [B] — final slot weight
    valid: Array  # bool [B] — final slot validity
    dirty_v: Array  # int32 [B] — endpoint to dirty (δE direct rule); V padding
    touched_src: Array  # int32 [B] — update source (degree-retune rule); V padding
    ell_row: Array  # int32 [B] — ELL cell writes (backend="ell"); V padding
    ell_col: Array  # int32 [B]
    ell_nbr: Array  # int32 [B]
    ell_w: Array  # f32  [B]


def batched_step(
    cfg: EngineConfig, state: EngineState, g: GraphArrays, upd: UpdateBatch
) -> tuple[EngineState, GraphArrays, MaintainStats]:
    """Fold one δE chunk into the graph arrays and run ONE maintenance sweep.

    This is the device-side twin of ``DiffIFE.apply_updates``: edge scatter,
    degree refresh, dirty-mask construction and the ``lax.while_loop`` sweep
    compile into a single program.  ``DiffIFE`` jits it with donated
    ``(state, g)`` so the stores update in place (no per-update host round
    trip, no buffer churn); host work per chunk is an O(B) encode.
    """
    v = cfg.num_vertices
    with jax.named_scope("cqp.edge_scatter"):
        src = g.src.at[upd.slot].set(upd.src, mode="drop")
        dst = g.dst.at[upd.slot].set(upd.dst, mode="drop")
        weight = g.weight.at[upd.slot].set(upd.weight, mode="drop")
        valid = g.valid.at[upd.slot].set(upd.valid, mode="drop")
        # degrees recomputed from the edge list — O(E) on-device, far below one
        # sweep iteration, and immune to host/device drift
        live = valid.astype(jnp.int32)
        out_degree = jax.ops.segment_sum(live, src, num_segments=v)
        in_degree = jax.ops.segment_sum(live, dst, num_segments=v)
        nbr, ell_w = g.nbr, g.ell_w
        if cfg.backend in ("ell", "fused"):
            nbr = nbr.at[upd.ell_row, upd.ell_col].set(upd.ell_nbr, mode="drop")
            ell_w = ell_w.at[upd.ell_row, upd.ell_col].set(upd.ell_w, mode="drop")
        g2 = GraphArrays(src, dst, weight, valid, out_degree, in_degree, nbr, ell_w)

        dirty = jnp.zeros(v + 1, bool).at[upd.dirty_v].set(True)[:v]
        if cfg.weight_from_degree:
            # outdeg(u) changed → every out-message of u retunes (δE dirty rule)
            tsrc = jnp.zeros(v + 1, bool).at[upd.touched_src].set(True)[:v]
            hit = (tsrc[g2.src] & g2.valid).astype(jnp.int32)
            dirty = dirty | (jax.ops.segment_max(hit, g2.dst, num_segments=v) > 0)

    new_state, stats = maintain(cfg, state, g2, _dirty_2d(cfg, dirty))
    return new_state, g2, stats


def _sum_stats(a: MaintainStats, b: MaintainStats) -> MaintainStats:
    return MaintainStats(*(x + y for x, y in zip(a, b)))


def _named(name: str, fn, *static):
    """``fn`` with its leading ``static`` arguments bound, as a function
    called ``name``: ``jax.jit`` names the program after it, where a
    ``functools.partial`` shows as ``jit__unknown``."""

    def call(*args):
        return fn(*static, *args)

    call.__name__ = call.__qualname__ = name
    return call


def _span_stats(stats: MaintainStats | None) -> dict:
    """Sweep attribution for trace spans: scalar counters plus the
    per-iteration size series trimmed to the iterations actually run."""
    if stats is None:
        return {}
    out = {k: int(getattr(stats, k)) for k in MaintainStats.SCALAR_FIELDS}
    n = min(max(out["iters_run"], 0), ITER_TRACE)
    out["sched_sizes"] = [int(x) for x in stats.sched_sizes[:n]]
    out["frontier_sizes"] = [int(x) for x in stats.frontier_sizes[:n]]
    return out


# --------------------------------------------------------------------------- host-facing wrapper
class DiffIFE:
    """Continuous-query processor: owns the dynamic graph + engine state.

    ``DiffIFE`` is the host driver (the GDBMS's continuous query processor);
    all device work happens in the pure functions above, jitted per graph
    capacity so update batches never recompile.

    Two ingestion paths:

    * :meth:`apply_updates` — per-batch host path: mutate the host graph,
      re-upload the device view, run one sweep.  Simple, but each batch pays
      a host round trip + full graph transfer.
    * :meth:`apply_updates_batched` — the throughput path: updates are folded
      in fixed-shape chunks of ``batch_capacity`` through the donated-buffer
      :func:`batched_step`, so the jit cache is hit once per chunk and the
      graph/stores never leave the device.

    With ``cfg.backend == "ell"`` the bucketed in-adjacency rides along; its
    width ``D`` is kept fixed across updates (host :class:`EllIndex` mirror)
    and grows geometrically — with a one-off re-trace — only when a vertex's
    in-degree outruns it.

    With ``mesh`` given (data axis > 1), every per-vertex carry partitions by
    destination vertex over the mesh ``data`` axis and both ingestion paths
    dispatch through ``shard_map`` (:func:`maintain_sharded` /
    :func:`batched_step_sharded`); the edge list moves into the
    :class:`ShardIndex` cell layout (cells grouped by owning shard, host
    mirror kept in sync per chunk) and grows geometrically per shard — with
    a one-off re-trace, and a J-store row permutation under VDC — when a
    shard's cells run out.

    **Query slot pool** (DESIGN.md §9): the leading Q axis is a padded pool
    of query slots gated by ``state.active``.  :meth:`register_slot` claims a
    free slot (growing the pool geometrically — one re-trace — when none is
    left) and initializes the new query's trace *in-engine*: one maintenance
    sweep whose per-query dirty mask seeds only the new row, so every other
    registered query is scheduled for zero work.  :meth:`deregister_slot`
    zeroes the slot's diff-store rows and returns the accounted bytes freed.
    """

    def __init__(
        self,
        cfg: EngineConfig,
        graph: DynamicGraph,
        init: np.ndarray | Array,
        *,
        batch_capacity: int = 32,
        mesh: Mesh | None = None,
        active: np.ndarray | None = None,
        drop_rows: list[dr.DropConfig] | None = None,
        join_rows: list[bool] | None = None,
    ) -> None:
        self.cfg = cfg
        self.graph = graph
        self.batch_capacity = int(batch_capacity)
        self.mesh = mesh
        self.num_shards = int(mesh.shape[DATA_AXIS]) if mesh is not None else 1
        if self.num_shards > 1 and cfg.num_vertices % self.num_shards:
            raise ValueError(
                f"num_vertices {cfg.num_vertices} not divisible by the mesh "
                f"data axis ({self.num_shards})"
            )
        self._ell_width = 0
        self._ell_index: EllIndex | None = None
        self._shard_index: ShardIndex | None = None
        self.g = self._device_graph(graph.snapshot())
        num_rows = (
            self.num_shards * self._shard_index.shard_capacity
            if self._shard_index is not None
            else graph.capacity
        )
        self.state = make_state(
            cfg,
            jnp.asarray(init, jnp.float32),
            num_rows,
            active=active,
            drop_rows=drop_rows,
            join_rows=join_rows,
        )
        if self.num_shards > 1:
            self.state = self._on_mesh(self.state, _state_pspecs(self.state))
        # descending so pop() hands out the lowest free slot first
        self._free_slots: list[int] = sorted(
            (
                q
                for q in range(cfg.num_queries)
                if active is not None and not bool(active[q])
            ),
            reverse=True,
        )
        self._build_dispatch()
        self.last_stats: MaintainStats | None = None
        # DroppedVT records lost to Det-Drop evictions DURING sheds (policy
        # rewrites).  Sweep-time losses surface per sweep in
        # MaintainStats.det_overflow; a shed runs between sweeps, so its
        # losses would otherwise vanish from telemetry entirely.
        self.det_overflow_shed = 0
        # cumulative scheduled vertex-reruns across all sweeps: the shared
        # recompute-volume signal apportioned to the Join operator (dropping
        # a join trades its stored messages for exactly this recomputation)
        self._sched_total = 0
        # initial computation: every vertex dirty, empty store (inactive
        # slots are masked out of the schedule by ``state.active``); an
        # all-inactive pool (the session's deferred-register path) has
        # nothing to compute and skips the dispatch entirely
        if active is None or bool(np.asarray(active).any()):
            self._run_counted(np.ones(cfg.num_vertices, dtype=bool))

    def _build_dispatch(self) -> None:
        """(Re)jit the two dispatch paths for the current static config.

        Each program is jitted under a name of its own, which names its
        module in the device trace (``jit_cqp_chunk_step``, ...)."""
        if self.num_shards > 1:
            static = (self.cfg, self.mesh)
            self._maintain = jax.jit(
                _named("cqp_maintain_sharded", maintain_sharded, *static)
            )
            self._step = jax.jit(
                _named("cqp_chunk_step_sharded", batched_step_sharded, *static),
                donate_argnums=(0, 1),
            )
        else:
            self._maintain = jax.jit(_named("cqp_maintain", maintain, self.cfg))
            self._step = jax.jit(
                _named("cqp_chunk_step", batched_step, self.cfg),
                donate_argnums=(0, 1),
            )
        # governor reclamation primitive; slot is traced so every rewrite of
        # any slot reuses one compiled program
        self._shed = jax.jit(_named("cqp_shed", shed_slot, self.cfg))

    # ------------------------------------------------------------ device views
    def _device_graph(self, snap: GraphSnapshot) -> GraphArrays:
        if self.num_shards > 1:
            return self._device_graph_sharded(snap)
        if self.cfg.backend in ("ell", "fused"):
            g = GraphArrays.from_snapshot(
                snap, backend=self.cfg.backend, ell_min_width=self._ell_width
            )
            self._ell_width = g.ell_width
            self._ell_index = EllIndex(snap, self._ell_width)
            return g
        return GraphArrays.from_snapshot(snap)

    def _device_graph_sharded(self, snap: GraphSnapshot) -> GraphArrays:
        if self._shard_index is None:
            self._shard_index = ShardIndex(snap, self.num_shards)
        src, dst, w, valid = self._shard_index.edge_arrays(snap)
        nbr = ell_w = None
        if self.cfg.backend in ("ell", "fused"):
            # ELL rows are keyed by destination, so the [V, D] view shards
            # row-wise as-is; neighbour ids stay global (the kernel gathers
            # from the all-gathered state row).
            nbr_np, w_np, width = snap.to_ell(min_width=self._ell_width)
            self._ell_width = width
            self._ell_index = EllIndex(snap, width)
            nbr, ell_w = jnp.asarray(nbr_np), jnp.asarray(w_np)
        g = GraphArrays(
            src=jnp.asarray(src),
            dst=jnp.asarray(dst),
            weight=jnp.asarray(w),
            valid=jnp.asarray(valid),
            out_degree=jnp.asarray(snap.out_degree, jnp.int32),
            in_degree=jnp.asarray(snap.in_degree, jnp.int32),
            nbr=nbr,
            ell_w=ell_w,
        )
        return self._on_mesh(g, _graph_pspecs(g))

    def _on_mesh(self, tree, specs):
        """Lay ``tree`` out on the mesh as the sharded programs return it, so
        their first call compiles the program every later call reuses."""
        return jax.device_put(
            tree,
            jax.tree.map(
                lambda spec: NamedSharding(self.mesh, spec),
                specs,
                is_leaf=lambda x: isinstance(x, P),
            ),
        )

    def _shard_sync(self, ops, snap: GraphSnapshot | None = None) -> list | None:
        """Fold resolved ops into the shard index; regrow on overflow.

        Returns the coalesced cell writes, or None when the index had to be
        rebuilt (the caller must then re-upload the full edge layout).  The
        snapshot is only needed on the overflow path — callers without one at
        hand (the per-chunk batched loop) let it be taken lazily there, so
        the hot path stays O(B) on the host."""
        try:
            return self._shard_index.writes_for(ops)
        except ShardOverflow:
            self._regrow_shards(snap if snap is not None else self.graph.snapshot())
            return None

    def _regrow_shards(self, snap: GraphSnapshot) -> None:
        """Rebuild the shard layout at 2× per-shard capacity (one re-trace).

        VDC's per-edge-cell J store follows its edges to the new cells; cells
        without a surviving edge start empty (the implicit-``j0`` fallback is
        exact for both fresh inserts and vacated cells)."""
        old = self._shard_index
        self._shard_index = ShardIndex(
            snap, self.num_shards, min_capacity=old.shard_capacity * 2
        )
        if self.state.jstore is not None:
            size = self.num_shards * self._shard_index.shard_capacity
            idx = np.full(size, -1, np.int32)
            for slot, lin in self._shard_index.cell_of.items():
                idx[lin] = old.cell_of.get(slot, -1)
            self.state = self.state._replace(
                jstore=ds.gather_rows(self.state.jstore, jnp.asarray(idx))
            )

    def _run(self, dirty: np.ndarray) -> None:
        self.state, stats = self._maintain(self.state, self.g, jnp.asarray(dirty))
        self.last_stats = jax.tree.map(jax.device_get, stats)

    def _run_counted(self, dirty: np.ndarray) -> None:
        """_run + fold the sweep into the cumulative recompute-volume signal
        (the batched path folds its own totals, fallback sweeps included)."""
        self._run(dirty)
        self._sched_total += int(self.last_stats.scheduled)

    def _dirty_mask(self, touched, snap: GraphSnapshot) -> np.ndarray:
        dirty = np.zeros(self.cfg.num_vertices, dtype=bool)
        for (u, v) in touched:
            dirty[v] = True
            if self.cfg.weight_from_degree:
                # outdeg(src) changed → every out-message of src retunes
                dirty[snap.dst[(snap.src == u) & snap.valid]] = True
        return dirty

    # ------------------------------------------------------------- ingestion
    def apply_updates(self, updates) -> MaintainStats:
        """Ingest one δE batch and maintain all registered queries."""
        with obs_trace.span(
            "sweep", "sweep", pid="engine:dense", shards=self.num_shards
        ) as sp:
            ops = self.graph.apply_batch_resolved(updates)
            snap = self.graph.snapshot()
            if self.num_shards > 1:
                self._shard_sync(ops, snap)  # keep cells stable (VDC)
            self.g = self._device_graph(snap)
            touched = [(u, v) for (_k, _s, u, v, _w) in ops]
            self._run_counted(self._dirty_mask(touched, snap))
            if sp.live:
                sp.set(num_updates=len(ops), **_span_stats(self.last_stats))
        return self.last_stats

    def _full_sweep_fallback(self, ops, total: MaintainStats) -> MaintainStats:
        """Re-upload the full device graph and run one host-path sweep (the
        once-per-growth escape hatch of the batched stream)."""
        with obs_trace.span(
            "full_sweep_fallback", "sweep", pid="engine:dense", num_ops=len(ops)
        ):
            snap = self.graph.snapshot()
            self.g = self._device_graph(snap)
            touched = [(u, v) for (_k, _s, u, v, _w) in ops]
            self._run(self._dirty_mask(touched, snap))
        return _sum_stats(total, self.last_stats)

    def apply_updates_batched(
        self, updates, batch_size: int | None = None
    ) -> MaintainStats:
        """Stream a δE log through the donated-buffer batched step.

        The log is folded in fixed-shape chunks of ``batch_size`` (default:
        ``batch_capacity``); per chunk ONE jitted call scatters the edge
        slots, refreshes degrees, builds the dirty mask on device and runs
        the maintenance sweep.  Returns the cumulative stats over the log.

        Spans, per chunk: ``ingest`` (host graph update, shard/ELL writes,
        encode) and ``dispatch`` (the enqueue of the asynchronous chunk
        program, not its device time); per log: ``sync``, the one wait for
        the device, when the summed stats come to the host.
        """
        b = int(batch_size if batch_size is not None else self.batch_capacity)
        updates = list(updates)
        total = zeros_stats()
        with obs_trace.span(
            "update_batch",
            "update_batch",
            pid="engine:dense",
            num_updates=len(updates),
            chunk_size=b,
            shards=self.num_shards,
        ) as outer:
            for lo in range(0, len(updates), b):
                with obs_trace.span(
                    "ingest", "ingest", pid="engine:dense", chunk_lo=lo
                ):
                    ops = self.graph.apply_batch_resolved(updates[lo : lo + b])
                    upd = self._ingest_chunk(ops, b) if ops else None
                if not ops:
                    continue
                if upd is None:
                    # a shard's cells or a vertex's ELL row overflowed: the
                    # layout was regrown, one full-view sweep for this chunk
                    total = self._full_sweep_fallback(ops, total)
                    continue
                with obs_trace.span(
                    "dispatch",
                    "dispatch",
                    pid="engine:dense",
                    chunk_lo=lo,
                    num_ops=len(ops),
                    backend=self.cfg.backend,
                ):
                    self.state, self.g, stats = self._step(self.state, self.g, upd)
                    # accumulate on device — one host sync per log, not per chunk
                    total = _sum_stats(total, stats)
            with obs_trace.span("sync", "sync", pid="engine:dense"):
                self.last_stats = jax.tree.map(jax.device_get, total)
            if outer.live:
                outer.set(**_span_stats(self.last_stats))
        self._sched_total += int(self.last_stats.scheduled)
        return self.last_stats

    def _ingest_chunk(self, ops, b: int) -> UpdateBatch | None:
        """The device encoding of one chunk's resolved ops, with the shard
        and ELL mirrors brought up to date; None when a layout overflowed
        and was regrown, so the chunk needs a full-view sweep."""
        shard_writes = None
        if self.num_shards > 1:
            shard_writes = self._shard_sync(ops)
            if shard_writes is None:
                return None  # jstore rows already permuted to the new cells
        ell_writes: list = []
        if self.cfg.backend in ("ell", "fused"):
            try:
                ell_writes = self._ell_index.writes_for(ops)
            except EllOverflow:
                # a vertex outran the fixed D: grow geometrically (one
                # re-trace on the full-view sweep)
                self._ell_width = max(8, self._ell_width * 2)
                return None
        return self._encode_chunk(ops, ell_writes, b, shard_writes)

    def _encode_chunk(self, ops, ell_writes, b: int, shard_writes=None) -> UpdateBatch:
        """Host O(B) encode of resolved ops → fixed-shape UpdateBatch."""
        if len(ops) > b:
            raise ValueError(f"chunk of {len(ops)} ops exceeds capacity {b}")
        v = self.cfg.num_vertices
        cap = (
            self.num_shards * self._shard_index.shard_capacity
            if shard_writes is not None
            else self.graph.capacity
        )
        slot = np.full(b, cap, np.int32)
        src = np.zeros(b, np.int32)
        dst = np.zeros(b, np.int32)
        weight = np.zeros(b, np.float32)
        valid = np.zeros(b, bool)
        dirty_v = np.full(b, v, np.int32)
        touched_src = np.full(b, v, np.int32)
        ell_row = np.full(b, v, np.int32)
        ell_col = np.zeros(b, np.int32)
        ell_nbr = np.zeros(b, np.int32)
        ell_wv = np.zeros(b, np.float32)
        if shard_writes is not None:
            # sharded layout: coalesced cell writes carry the final contents
            for j, wr in enumerate(shard_writes):
                slot[j] = wr.lin
                src[j], dst[j] = wr.src, wr.dst
                weight[j], valid[j] = wr.weight, wr.valid
        else:
            # final slot contents come from the already-updated host graph, so
            # a delete+reinsert of one slot inside a chunk coalesces to one row
            for j, s in enumerate(dict.fromkeys(op[1] for op in ops)):
                slot[j] = s
                src[j] = self.graph.src[s]
                dst[j] = self.graph.dst[s]
                weight[j] = self.graph.weight[s]
                valid[j] = self.graph.valid[s]
        for j, (_kind, _s, u, d, _w) in enumerate(ops):
            dirty_v[j] = d
            touched_src[j] = u
        for j, wr in enumerate(ell_writes):
            ell_row[j], ell_col[j] = wr.row, wr.col
            ell_nbr[j], ell_wv[j] = wr.nbr_val, wr.w_val
        return UpdateBatch(
            slot=jnp.asarray(slot),
            src=jnp.asarray(src),
            dst=jnp.asarray(dst),
            weight=jnp.asarray(weight),
            valid=jnp.asarray(valid),
            dirty_v=jnp.asarray(dirty_v),
            touched_src=jnp.asarray(touched_src),
            ell_row=jnp.asarray(ell_row),
            ell_col=jnp.asarray(ell_col),
            ell_nbr=jnp.asarray(ell_nbr),
            ell_w=jnp.asarray(ell_wv),
        )

    # ------------------------------------------------------- query slot pool
    def _clear_slot_state(self, st: EngineState, slot: int) -> EngineState:
        """Zero every per-slot row: diff stores, DroppedVT, repair counts."""

        def clear_store(store: ds.DiffStore) -> ds.DiffStore:
            return ds.DiffStore(
                iters=store.iters.at[slot].set(ds.IMAX),
                vals=store.vals.at[slot].set(0.0),
                count=store.count.at[slot].set(0),
            )

        drop = st.drop
        if drop.det is not None:
            drop = drop._replace(det=clear_store(drop.det))
        if drop.flt is not None:
            drop = drop._replace(
                flt=drop.flt._replace(drop.flt.bits.at[slot].set(False))
            )
        return st._replace(
            dstore=clear_store(st.dstore),
            jstore=None if st.jstore is None else clear_store(st.jstore),
            drop=drop,
            repair_counts=st.repair_counts.at[slot].set(0),
        )

    def register_slot(
        self,
        init_row: np.ndarray | Array,
        drop_cfg: dr.DropConfig | None = None,
        materialize_join: bool | None = None,
    ) -> int:
        """Claim a slot for a new query and compute its trace in-engine.

        ``init_row`` is the query's D_0 ([V]); ``drop_cfg`` its selection
        policy (default: the engine's).  The slot's trace is initialized by
        one maintenance sweep whose dirty mask seeds only the new row — the
        sweep *is* the static IFE run for that query while every other
        registered query is scheduled for zero work.  Returns the slot id.
        """
        return self.register_slots([(init_row, drop_cfg, materialize_join)])[0]

    def register_slots(self, requests: list[tuple]) -> list[int]:
        """Batch form of :meth:`register_slot`: claim one slot per
        (init_row, drop_cfg[, materialize_join]) request and initialize ALL
        the new traces in a single maintenance sweep (the per-query dirty
        mask seeds exactly the new rows).  ``materialize_join`` gates the
        slot's Join store on vdc engines (None → materialize)."""
        requests = [
            (req[0], req[1], req[2] if len(req) > 2 else None)
            for req in requests
        ]
        for _row, drop_cfg, _jm in requests:
            if drop_cfg is not None and drop_cfg.enabled():
                if drop_cfg.mode != self.cfg.drop.mode:
                    raise ValueError(
                        f"plan drop mode {drop_cfg.mode!r} does not match the "
                        f"engine's DroppedVT representation "
                        f"{self.cfg.drop.mode!r}"
                    )
        while len(self._free_slots) < len(requests):
            self._grow_queries()
        slots = []
        st = self.state
        for init_row, drop_cfg, join_flag in requests:
            slot = self._free_slots.pop()
            row = jnp.asarray(init_row, jnp.float32)
            st = self._clear_slot_state(st, slot)
            st = st._replace(
                init=st.init.at[slot].set(row),
                cur=st.cur.at[slot].set(row),
                active=st.active.at[slot].set(True),
            )
            if st.join_mat is not None:
                st = st._replace(
                    join_mat=st.join_mat.at[slot].set(
                        True if join_flag is None else bool(join_flag)
                    )
                )
            if st.drop.params is not None:
                st = st._replace(
                    drop=st.drop._replace(
                        params=dr.set_params_row(
                            st.drop.params,
                            slot,
                            drop_cfg if drop_cfg is not None else self.cfg.drop,
                        )
                    )
                )
            slots.append(slot)
        self.state = st
        dirty = np.zeros((self.cfg.num_queries, self.cfg.num_vertices), bool)
        dirty[slots] = True
        self._run_counted(dirty)
        return slots

    def deregister_slot(self, slot: int) -> int:
        """Retire a query slot: zero its diff-store rows, free the slot.

        Returns the accounted difference bytes released (the slot's D/J/
        DroppedVT rows; Bloom bits are fixed-size and only zeroed).
        """
        if not bool(np.asarray(self.state.active)[slot]):
            raise ValueError(f"slot {slot} is not active")
        freed = self.slot_nbytes(slot)
        ident = jnp.full(
            (self.cfg.num_vertices,), self.cfg.semiring.identity, jnp.float32
        )
        st = self._clear_slot_state(self.state, slot)
        st = st._replace(
            init=st.init.at[slot].set(ident),
            cur=st.cur.at[slot].set(ident),
            active=st.active.at[slot].set(False),
        )
        if st.join_mat is not None:  # freed slots rejoin the pool materialized
            st = st._replace(join_mat=st.join_mat.at[slot].set(True))
        if st.drop.params is not None:
            st = st._replace(
                drop=st.drop._replace(
                    params=dr.set_params_row(st.drop.params, slot, dr.DropConfig())
                )
            )
        if st.drop.det is not None:
            # re-anchor the dropped-VT horizon from the surviving rows so a
            # retired heavy-drop query stops inflating every later sweep's
            # trip count (Bloom mode keeps the old anchor: bits can't delete)
            live = jnp.where(st.drop.det.iters < ds.IMAX, st.drop.det.iters, -1)
            st = st._replace(drop=st.drop._replace(max_iter=live.max()))
        self.state = st
        self._free_slots.append(slot)
        self._free_slots.sort(reverse=True)
        return freed

    def slot_nbytes(self, slot: int) -> int:
        """Accounted difference bytes held by one query slot: its D/J diff
        rows, its DroppedVT records (det rows, or its packed Bloom row), and
        its DropParams row — so summing over the live slots reproduces
        :func:`nbytes_accounted` exactly."""
        total = int(np.asarray(self.state.dstore.count[slot]).sum()) * 8
        if self.state.jstore is not None:
            total += int(np.asarray(self.state.jstore.count[slot]).sum()) * 8
        if self.state.drop.det is not None:
            total += int(np.asarray(self.state.drop.det.count[slot]).sum()) * 4
        if self.cfg.drop.enabled() and bool(np.asarray(self.state.active)[slot]):
            if self.state.drop.flt is not None:
                total += (self.state.drop.flt.num_bits + 7) // 8
            if self.state.drop.params is not None:
                total += dr.PARAMS_ROW_NBYTES
        return total

    def nbytes_per_query(self) -> dict[int, int]:
        """slot → accounted bytes, for every live slot (the governor's
        per-[Q] memory breakdown).  One device→host pull per array — this
        runs on every enforcement pass, so per-slot fetches would cost
        O(Q) syncs per batch."""
        per = np.asarray(self.state.dstore.count).sum(axis=1) * 8
        if self.state.jstore is not None:
            per = per + np.asarray(self.state.jstore.count).sum(axis=1) * 8
        if self.state.drop.det is not None:
            per = per + np.asarray(self.state.drop.det.count).sum(axis=1) * 4
        fixed = 0
        if self.cfg.drop.enabled():
            if self.state.drop.flt is not None:
                fixed += (self.state.drop.flt.num_bits + 7) // 8
            if self.state.drop.params is not None:
                fixed += dr.PARAMS_ROW_NBYTES
        return {s: int(per[s]) + fixed for s in self.active_slots()}

    def nbytes_per_operator(self) -> dict[int, dict[str, int]]:
        """slot → {op_id → accounted bytes}: the per-query breakdown refined
        to the operators that own difference stores.  ``"iterate"`` carries
        the change-point rows plus the slot's DroppedVT/params footprint;
        ``"join"`` (vdc engines) its J-store rows.  Per slot the operator
        bytes sum exactly to :meth:`nbytes_per_query`'s entry."""
        per_d = np.asarray(self.state.dstore.count).sum(axis=1) * 8
        if self.state.drop.det is not None:
            per_d = per_d + np.asarray(self.state.drop.det.count).sum(axis=1) * 4
        fixed = 0
        if self.cfg.drop.enabled():
            if self.state.drop.flt is not None:
                fixed += (self.state.drop.flt.num_bits + 7) // 8
            if self.state.drop.params is not None:
                fixed += dr.PARAMS_ROW_NBYTES
        per_j = (
            None
            if self.state.jstore is None
            else np.asarray(self.state.jstore.count).sum(axis=1) * 8
        )
        out: dict[int, dict[str, int]] = {}
        for s in self.active_slots():
            ops = {"iterate": int(per_d[s]) + fixed}
            if per_j is not None:
                ops["join"] = int(per_j[s])
            out[s] = ops
        return out

    def recompute_cost_per_query(self) -> dict[int, int]:
        """slot → cumulative dropped-diff repair count (the engine's cheap
        online recompute-cost signal, Fig. 6b's counter per query row)."""
        per = np.asarray(self.state.repair_counts).sum(axis=1)
        return {s: int(per[s]) for s in self.active_slots()}

    def recompute_cost_per_operator(self) -> dict[int, dict[str, int]]:
        """slot → {op_id → cumulative recompute cost}.  ``"iterate"`` is the
        slot's dropped-diff repair count; ``"join"`` (vdc engines) the
        cumulative scheduled vertex-rerun volume apportioned evenly across
        live slots — message recomputation tracks sweep breadth, which is
        shared, so the join signal ranks queries by bytes alone."""
        per = np.asarray(self.state.repair_counts).sum(axis=1)
        live = self.active_slots()
        share = self._sched_total // max(len(live), 1)
        out: dict[int, dict[str, int]] = {}
        for s in live:
            ops = {"iterate": int(per[s])}
            if self.state.jstore is not None:
                ops["join"] = int(share)
            out[s] = ops
        return out

    def set_join_store(self, slot: int, materialize: bool) -> int:
        """Flip one slot's Join-operator storage policy (vdc engines).

        ``materialize=False`` drops the slot's join differences completely
        (§4): its J-store rows are zeroed — the accounted bytes released are
        returned — and subsequent sweeps recompute its messages on demand
        (``join_mat`` is a traced [Q] row: no recompile).  No DroppedVT
        record is needed: complete dropping is deterministic, so repair
        needs no per-record memory.

        ``materialize=True`` re-materializes: the slot's ``cur`` is reset to
        its D_0 and one maintenance sweep re-walks the stored trajectory
        (register-convergence), rewriting the J rows as it goes.  Answers
        are recomputed exactly; returns 0.
        """
        if not bool(np.asarray(self.state.active)[slot]):
            raise ValueError(f"slot {slot} is not active")
        if self.state.jstore is None:
            if materialize:
                raise ValueError(
                    "engine built without a join store (mode='jod'); open "
                    "the session with a join-materializing plan in the "
                    "first registered batch"
                )
            return 0  # JOD engines hold no join differences to begin with
        already = bool(np.asarray(self.state.join_mat)[slot])
        if materialize == already:
            return 0
        st = self.state
        if not materialize:
            freed = int(np.asarray(st.jstore.count[slot]).sum()) * 8
            jstore = ds.DiffStore(
                iters=st.jstore.iters.at[slot].set(ds.IMAX),
                vals=st.jstore.vals.at[slot].set(0.0),
                count=st.jstore.count.at[slot].set(0),
            )
            self.state = st._replace(
                jstore=jstore, join_mat=st.join_mat.at[slot].set(False)
            )
            return freed
        self.state = st._replace(
            cur=st.cur.at[slot].set(st.init[slot]),
            join_mat=st.join_mat.at[slot].set(True),
        )
        dirty = np.zeros((self.cfg.num_queries, self.cfg.num_vertices), bool)
        dirty[slot] = True
        self._run_counted(dirty)
        return 0

    def set_drop_params(
        self, slot: int, drop_cfg: dr.DropConfig, op_id: str = "iterate"
    ) -> int:
        """Rewrite a LIVE slot's drop policy for ONE operator.

        ``op_id="iterate"`` (default) rewrites the slot's §5 selection
        params in place (no recompile — the params are traced ``[Q]`` rows)
        and sheds its stored diffs under the new policy.  ``op_id="join"``
        routes to :meth:`set_join_store` — an enabled config (complete
        dropping) drops the slot's join trace, a disabled one
        re-materializes it.  Returns the accounted bytes released (≥ 0 for
        iterate: a shed trades 8 B change points for ≤4 B DroppedVT records
        or Bloom bits).
        """
        if op_id == "join":
            if drop_cfg.enabled() and not drop_cfg.drops_all():
                raise ValueError(
                    "the join's differences drop completely (p ≥ 1); "
                    "partial join dropping is unsupported"
                )
            return self.set_join_store(slot, not drop_cfg.enabled())
        if op_id != "iterate":
            raise ValueError(
                f"operator {op_id!r} owns no engine difference store"
            )
        if not bool(np.asarray(self.state.active)[slot]):
            raise ValueError(f"slot {slot} is not active")
        if self.state.drop.params is None:
            if drop_cfg.enabled():
                raise ValueError(
                    "cannot enable dropping on an engine built without a "
                    "DroppedVT representation (cfg.drop.mode='none')"
                )
            return 0
        if drop_cfg.enabled() and drop_cfg.mode != self.cfg.drop.mode:
            raise ValueError(
                f"drop mode {drop_cfg.mode!r} does not match the engine's "
                f"DroppedVT representation {self.cfg.drop.mode!r}"
            )
        before = self.slot_nbytes(slot)
        self.state = self.state._replace(
            drop=self.state.drop._replace(
                params=dr.set_params_row(self.state.drop.params, slot, drop_cfg)
            )
        )
        if drop_cfg.enabled():
            ovf_before = int(self.state.drop.det_overflow)
            self.state = self._shed(self.state, self.g, jnp.int32(slot))
            self.det_overflow_shed += int(self.state.drop.det_overflow) - ovf_before
        return before - self.slot_nbytes(slot)

    def active_slots(self) -> list[int]:
        return [int(q) for q in np.nonzero(np.asarray(self.state.active))[0]]

    @property
    def slot_capacity(self) -> int:
        return self.cfg.num_queries

    def _grow_queries(self) -> None:
        """Double the slot pool (geometric growth, one re-trace).

        Every [Q, ...] leaf pads along the query axis: stores stay empty,
        init/cur pad with the semiring identity, new slots join the free
        list.  The next dispatch retraces once for the new static Q.
        """
        old_q = self.cfg.num_queries
        new_q = max(1, old_q * 2)
        pad = new_q - old_q

        def padq(x, fill, dtype=None):
            x = np.asarray(x)
            block = np.full((pad, *x.shape[1:]), fill, dtype or x.dtype)
            return jnp.asarray(np.concatenate([x, block], axis=0))

        def pad_store(store: ds.DiffStore) -> ds.DiffStore:
            return ds.DiffStore(
                iters=padq(store.iters, np.iinfo(np.int32).max),
                vals=padq(store.vals, 0.0),
                count=padq(store.count, 0),
            )

        st = self.state
        drop = st.drop
        if drop.det is not None:
            drop = drop._replace(det=pad_store(drop.det))
        if drop.flt is not None:
            drop = drop._replace(flt=drop.flt._replace(padq(drop.flt.bits, False)))
        if drop.params is not None:
            fresh = dr.make_params(self.cfg.drop, pad)
            drop = drop._replace(
                params=dr.DropParams(
                    *(
                        jnp.concatenate([jnp.asarray(a), b])
                        for a, b in zip(drop.params, fresh)
                    )
                )
            )
        ident = self.cfg.semiring.identity
        self.state = EngineState(
            dstore=pad_store(st.dstore),
            jstore=None if st.jstore is None else pad_store(st.jstore),
            drop=drop,
            init=padq(st.init, ident),
            cur=padq(st.cur, ident),
            repair_counts=padq(st.repair_counts, 0),
            active=padq(st.active, False),
            join_mat=None if st.join_mat is None else padq(st.join_mat, True),
        )
        self.cfg = dataclasses.replace(self.cfg, num_queries=new_q)
        self._free_slots.extend(range(new_q - 1, old_q - 1, -1))
        self._build_dispatch()

    # ------------------------------------------------------------ durability
    def export_state(self) -> tuple[dict[str, np.ndarray], dict]:
        """(arrays, meta) snapshot of the difference trace.

        Arrays are *global* (device_get assembles sharded carries) and the
        VDC J store is converted from the mesh-dependent cell layout to the
        canonical edge-slot layout ``[Q, E_cap, S_J]`` — so a checkpoint
        taken at 8 shards is layout-independent and restores at any shard
        count (:meth:`import_state` scatters rows into the new cell layout).
        """
        st = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), self.state)
        arrays: dict[str, np.ndarray] = {}

        def put_store(prefix: str, store: ds.DiffStore) -> None:
            arrays[prefix + "/iters"] = np.asarray(store.iters)
            arrays[prefix + "/vals"] = np.asarray(store.vals)
            arrays[prefix + "/count"] = np.asarray(store.count)

        put_store("dstore", st.dstore)
        if st.jstore is not None:
            jst = st.jstore
            if self._shard_index is not None:
                idx = np.full(self.graph.capacity, -1, np.int32)
                for slot, lin in self._shard_index.cell_of.items():
                    idx[slot] = lin
                jst = jax.tree.map(
                    lambda x: np.asarray(jax.device_get(x)),
                    ds.gather_rows(self.state.jstore, jnp.asarray(idx)),
                )
            put_store("jstore", jst)
        drop = st.drop
        if drop.det is not None:
            put_store("drop_det", drop.det)
        if drop.flt is not None:
            arrays["drop_flt/bits"] = np.asarray(drop.flt.bits)
        arrays["drop/det_overflow"] = np.asarray(drop.det_overflow)
        arrays["drop/max_iter"] = np.asarray(drop.max_iter)
        if drop.params is not None:
            for f in dr.DropParams._fields:
                arrays[f"drop_params/{f}"] = np.asarray(getattr(drop.params, f))
        arrays["init"] = st.init
        arrays["cur"] = st.cur
        arrays["repair_counts"] = st.repair_counts
        arrays["active"] = st.active
        if st.join_mat is not None:
            arrays["join_mat"] = st.join_mat
        meta = {
            "slot_capacity": self.cfg.num_queries,
            "mode": self.cfg.mode,
            "free_slots": [int(s) for s in self._free_slots],
            "det_overflow_shed": int(self.det_overflow_shed),
            "sched_total": int(self._sched_total),
            "ell_width": int(self._ell_width),
        }
        return arrays, meta

    def import_state(self, arrays: dict, meta: dict) -> None:
        """Load a snapshot produced by :meth:`export_state`.

        The engine must have been constructed for the same restored graph
        and slot capacity (an all-inactive pool skips the initial sweep, so
        construction is cheap); the J store is scattered into *this* mesh's
        cell layout and every carry is placed via ``elastic.reshard`` when
        sharded.
        """
        if int(meta["slot_capacity"]) != self.cfg.num_queries:
            raise ValueError(
                f"checkpoint has {meta['slot_capacity']} query slots but the "
                f"engine was built with {self.cfg.num_queries}"
            )

        def get_store(prefix: str) -> ds.DiffStore:
            return ds.DiffStore(
                iters=jnp.asarray(arrays[prefix + "/iters"]),
                vals=jnp.asarray(arrays[prefix + "/vals"]),
                count=jnp.asarray(arrays[prefix + "/count"]),
            )

        jstore = None
        if "jstore/iters" in arrays:
            jstore = get_store("jstore")
            if self._shard_index is not None:
                size = self.num_shards * self._shard_index.shard_capacity
                idx = np.full(size, -1, np.int32)
                for slot, lin in self._shard_index.cell_of.items():
                    idx[lin] = slot
                jstore = ds.gather_rows(jstore, jnp.asarray(idx))
        det = get_store("drop_det") if "drop_det/iters" in arrays else None
        flt = None
        if "drop_flt/bits" in arrays:
            flt = bloom_lib.BloomFilter(
                jnp.asarray(arrays["drop_flt/bits"]), self.cfg.drop.bloom_hashes
            )
        params = None
        if "drop_params/p" in arrays:
            params = dr.DropParams(
                **{
                    f: jnp.asarray(arrays[f"drop_params/{f}"])
                    for f in dr.DropParams._fields
                }
            )
        state = EngineState(
            dstore=get_store("dstore"),
            jstore=jstore,
            drop=dr.DropState(
                det=det,
                flt=flt,
                det_overflow=jnp.asarray(arrays["drop/det_overflow"]),
                max_iter=jnp.asarray(arrays["drop/max_iter"]),
                params=params,
            ),
            init=jnp.asarray(arrays["init"]),
            cur=jnp.asarray(arrays["cur"]),
            repair_counts=jnp.asarray(arrays["repair_counts"]),
            active=jnp.asarray(arrays["active"]),
            join_mat=jnp.asarray(arrays["join_mat"]) if "join_mat" in arrays else None,
        )
        if self.num_shards > 1:
            from repro.runtime import elastic

            state = elastic.reshard(state, _state_pspecs(state), self.mesh)
        self.state = state
        self._free_slots = [int(s) for s in meta["free_slots"]]
        self.det_overflow_shed = int(meta["det_overflow_shed"])
        self._sched_total = int(meta["sched_total"])
        width = int(meta.get("ell_width", 0))
        if self.cfg.backend in ("ell", "fused") and width > self._ell_width:
            # the saved run had grown its bucketed-ELL width; match it so the
            # replayed suffix hits the same compiled shapes
            self._ell_width = width
            self.g = self._device_graph(self.graph.snapshot())
        self.last_stats = None

    # ------------------------------------------------------------------- api
    def answers(self) -> np.ndarray:
        return np.asarray(answers(self.cfg, self.state))

    def answers_row(self, slot: int) -> np.ndarray:
        """One query slot's final vertex states. [V]"""
        return np.asarray(self.state.cur[slot])

    def nbytes(self) -> int:
        return nbytes_accounted(self.cfg, self.state)

    def nbytes_per_device(self) -> list[int]:
        """Accounted bytes per shard of the vertex partition (unsharded: one
        entry — the whole store)."""
        if self.num_shards == 1:
            return [self.nbytes()]
        return nbytes_per_shard(self.cfg, self.state, self.num_shards)
