"""Dynamic property graph: host-side mutable store + device COO views.

The paper's graph model (§3.1): directed property graph, edge labels and
weights, update batches ``[(u, v, label, weight, +/-)]``.  A GDBMS keeps the
adjacency index on the host; the IFE compute consumes fixed-shape device
arrays.  We preallocate edge capacity so update batches never change array
shapes (no recompile), and mark deleted slots invalid.

Device layout is COO (``src``, ``dst``, ``w``, ``valid``) — the engine's
pure-JAX SpMV uses ``segment_min``/``segment_max``/``segment_sum`` over
``dst``.  The Pallas ``ell_spmv`` kernel consumes the bucketed-ELL view
produced by :meth:`GraphSnapshot.to_ell`.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Sequence

import numpy as np

# An update is (u, v, label, weight, +1|-1) as in the paper §3.1.
Update = tuple[int, int, int, float, int]

# A resolved op is (kind, slot, u, v, weight) where kind ∈ {"insert",
# "update", "delete"}: the slot-level effect of one accepted update
# ("update" = weight change in place; no-op deletions are filtered out).
ResolvedOp = tuple[str, int, int, int, float]

NO_LABEL = 0


@dataclasses.dataclass
class GraphSnapshot:
    """Immutable fixed-shape device-friendly view of the graph."""

    num_vertices: int
    src: np.ndarray  # int32 [E_cap]
    dst: np.ndarray  # int32 [E_cap]
    weight: np.ndarray  # float32 [E_cap]
    label: np.ndarray  # int32 [E_cap]
    valid: np.ndarray  # bool [E_cap]
    out_degree: np.ndarray  # int32 [V]
    in_degree: np.ndarray  # int32 [V]

    @property
    def capacity(self) -> int:
        return int(self.src.shape[0])

    @property
    def num_edges(self) -> int:
        return int(self.valid.sum())

    def degrees_total(self) -> np.ndarray:
        return self.out_degree + self.in_degree

    def to_ell(
        self, pad_to_multiple: int = 8, min_width: int = 0, row_multiple: int = 1
    ) -> tuple[np.ndarray, np.ndarray, int]:
        """In-adjacency in ELL layout (for the Pallas kernel).

        Returns ``(nbr, w)`` with shape ``[Vr, D]`` where ``D`` is the max
        in-degree rounded up; padded slots have ``nbr == V`` (a sentinel row;
        callers pad the state vector with the reduce identity at index V).
        ``min_width`` lets the continuous processor keep ``D`` fixed across
        update batches (a ``D`` change means a re-trace of the jitted sweep).

        ``row_multiple`` pads the ROW count to a multiple (``Vr ≥ V``) with
        all-sentinel rows, once, at build time — the kernels never pad or
        copy operands per call (their blocked grid needs the row count to be
        a block multiple; see ``ell_spmv``'s shape contract).  Padding rows
        gather only the identity, and callers slice their outputs back to V.
        """
        v = self.num_vertices
        live = self.valid
        indeg = np.bincount(self.dst[live], minlength=v)
        d = max(int(indeg.max()) if v else 0, min_width)
        d = max(pad_to_multiple, ((d + pad_to_multiple - 1) // pad_to_multiple) * pad_to_multiple)
        vr = ((v + row_multiple - 1) // row_multiple) * row_multiple
        nbr = np.full((vr, d), v, dtype=np.int32)
        w = np.zeros((vr, d), dtype=np.float32)
        fill = np.zeros(v, dtype=np.int64)
        for e in np.nonzero(live)[0]:
            u, t = int(self.src[e]), int(self.dst[e])
            nbr[t, fill[t]] = u
            w[t, fill[t]] = self.weight[e]
            fill[t] += 1
        return nbr, w, d


def edge_capacity(initial: Sequence[tuple], log: Sequence[tuple]) -> int:
    """Edge slots a run needs: the initial edges plus every insert of the
    ``(u, v, label, w, ±1)`` update log.  A delete frees its slot for reuse,
    so the live count never passes this."""
    return len(initial) + sum(1 for u in log if u[4] > 0)


class DynamicGraph:
    """Host-side dynamic graph with slot-recycling edge storage."""

    def __init__(
        self,
        num_vertices: int,
        edges: Sequence[tuple] | np.ndarray,
        *,
        capacity: int | None = None,
        weighted: bool = True,
    ) -> None:
        edges = list(edges)
        n = len(edges)
        cap = capacity if capacity is not None else max(16, int(n * 1.5))
        if cap < n:
            raise ValueError("capacity below initial edge count")
        self.num_vertices = int(num_vertices)
        self.weighted = weighted
        self.src = np.full(cap, 0, dtype=np.int32)
        self.dst = np.full(cap, 0, dtype=np.int32)
        self.weight = np.zeros(cap, dtype=np.float32)
        self.label = np.zeros(cap, dtype=np.int32)
        self.valid = np.zeros(cap, dtype=bool)
        self.out_degree = np.zeros(self.num_vertices, dtype=np.int32)
        self.in_degree = np.zeros(self.num_vertices, dtype=np.int32)
        self._slot: dict[tuple[int, int, int], int] = {}
        self._free: list[int] = list(range(cap - 1, n - 1, -1))
        self.version = 0  # G_k
        if n == 0:
            return
        src = np.fromiter((e[0] for e in edges), np.int64, n)
        dst = np.fromiter((e[1] for e in edges), np.int64, n)
        if min(src.min(), dst.min()) < 0 or max(src.max(), dst.max()) >= num_vertices:
            raise ValueError(f"edge endpoint outside [0, {num_vertices})")
        self.src[:n], self.dst[:n] = src, dst
        if weighted:
            self.weight[:n] = np.fromiter(
                (e[2] if len(e) > 2 else 1.0 for e in edges), np.float64, n
            )
        else:
            self.weight[:n] = 1.0
        self.label[:n] = np.fromiter(
            (e[3] if len(e) > 3 else NO_LABEL for e in edges), np.int64, n
        )
        self.valid[:n] = True
        self.out_degree[:] = np.bincount(src, minlength=self.num_vertices)
        self.in_degree[:] = np.bincount(dst, minlength=self.num_vertices)
        # a repeated (u, v, label) keeps its last slot, as slot-by-slot
        # insertion would
        self._slot = dict(
            zip(zip(src.tolist(), dst.tolist(), self.label[:n].tolist()), range(n))
        )

    # ------------------------------------------------------------ durability
    def state_dict(self) -> tuple[dict[str, np.ndarray], dict]:
        """(arrays, meta) capturing the full mutable state.

        The free list is saved as an *ordered* array: slot recycling order
        decides which slot a replayed insert lands in, so replay determinism
        requires restoring it exactly — not recomputing it from ``valid``.
        """
        arrays = {
            "src": self.src.copy(),
            "dst": self.dst.copy(),
            "weight": self.weight.copy(),
            "label": self.label.copy(),
            "valid": self.valid.copy(),
            "out_degree": self.out_degree.copy(),
            "in_degree": self.in_degree.copy(),
            "free": np.asarray(self._free, dtype=np.int64),
        }
        meta = {
            "num_vertices": self.num_vertices,
            "weighted": self.weighted,
            "version": self.version,
        }
        return arrays, meta

    @classmethod
    def from_state(cls, meta: dict, arrays: dict) -> "DynamicGraph":
        g = cls(
            int(meta["num_vertices"]),
            [],
            capacity=int(arrays["src"].shape[0]),
            weighted=bool(meta["weighted"]),
        )
        for name in ("src", "dst", "weight", "label", "valid",
                     "out_degree", "in_degree"):
            getattr(g, name)[:] = arrays[name]
        g._free = [int(x) for x in arrays["free"]]
        g._slot = {
            (int(g.src[i]), int(g.dst[i]), int(g.label[i])): int(i)
            for i in np.nonzero(g.valid)[0]
        }
        g.version = int(meta["version"])
        return g

    # ------------------------------------------------------------------ api
    @property
    def num_edges(self) -> int:
        return int(self.valid.sum())

    @property
    def capacity(self) -> int:
        return int(self.src.shape[0])

    def snapshot(self) -> GraphSnapshot:
        return GraphSnapshot(
            num_vertices=self.num_vertices,
            src=self.src.copy(),
            dst=self.dst.copy(),
            weight=self.weight.copy(),
            label=self.label.copy(),
            valid=self.valid.copy(),
            out_degree=self.out_degree.copy(),
            in_degree=self.in_degree.copy(),
        )

    def apply_batch(self, updates: Iterable[Update]) -> list[tuple[int, int]]:
        """Apply one δE batch; returns the touched (src, dst) endpoints.

        Insertions of an existing (u, v, label) update the weight in place
        (the paper models weight updates as delete+insert; both forms are
        accepted).  Endpoints — not slots — are returned because a later
        insert in the same batch may recycle a freed slot.
        """
        return [(u, v) for (_kind, _slot, u, v, _w) in self.apply_batch_resolved(updates)]

    def apply_batch_resolved(self, updates: Iterable[Update]) -> list[ResolvedOp]:
        """Apply one δE batch, returning the slot-level effect of every
        accepted update (the device mirror the batched engine step scatters).
        """
        ops: list[ResolvedOp] = []
        for (u, v, lbl, w, sign) in updates:
            u, v, lbl = int(u), int(v), int(lbl)
            key = (u, v, lbl)
            if sign > 0:
                if key in self._slot:
                    i = self._slot[key]
                    self.weight[i] = float(w)
                    ops.append(("update", i, u, v, float(w)))
                else:
                    if not self._free:
                        raise MemoryError("edge capacity exhausted")
                    i = self._free.pop()
                    self.src[i], self.dst[i] = u, v
                    self.weight[i], self.label[i] = float(w), lbl
                    self.valid[i] = True
                    self._slot[key] = i
                    self.out_degree[u] += 1
                    self.in_degree[v] += 1
                    ops.append(("insert", i, u, v, float(w)))
            else:
                if key not in self._slot:
                    continue  # deleting a non-existent edge is a no-op
                i = self._slot.pop(key)
                self.valid[i] = False
                self._free.append(i)
                self.out_degree[u] -= 1
                self.in_degree[v] -= 1
                ops.append(("delete", i, u, v, float(w)))
        self.version += 1
        return ops

    def degree_percentile(self, pct: float) -> float:
        """Degree threshold at the given percentile (paper: τ_max = 80th)."""
        deg = self.degrees_total()
        return float(np.percentile(deg[deg > 0], pct)) if (deg > 0).any() else 0.0

    def degrees_total(self) -> np.ndarray:
        return self.out_degree + self.in_degree


@dataclasses.dataclass
class EllWrite:
    """One ELL cell assignment: ``nbr[row, col] = nbr_val; w[row, col] = w_val``."""

    row: int
    col: int
    nbr_val: int
    w_val: float


class EllOverflow(Exception):
    """A row ran out of ELL columns — the caller must rebuild at a wider D."""


class EllIndex:
    """Host mirror of the device ELL buffers (``GraphSnapshot.to_ell``).

    Tracks the (row = dst, col) cell of every live edge slot plus per-row free
    columns, so a δE batch becomes O(B) scatter writes on the device instead
    of an O(V·D) host rebuild + transfer.  Construction replays the exact fill
    order of :meth:`GraphSnapshot.to_ell` (ascending live slot index), so a
    freshly-built index agrees cell-for-cell with ``to_ell`` output.
    """

    def __init__(self, snap: GraphSnapshot, width: int) -> None:
        self.v = snap.num_vertices
        self.width = int(width)
        self.col_of: dict[int, tuple[int, int]] = {}  # edge slot → (row, col)
        self.fill = np.zeros(self.v, dtype=np.int64)
        self.free: dict[int, list[int]] = {}
        for e in np.nonzero(snap.valid)[0]:
            t = int(snap.dst[e])
            if self.fill[t] >= self.width:
                raise EllOverflow(f"in-degree of vertex {t} exceeds width {self.width}")
            self.col_of[int(e)] = (t, int(self.fill[t]))
            self.fill[t] += 1

    def _alloc(self, row: int) -> int:
        cols = self.free.get(row)
        if cols:
            return cols.pop()
        if self.fill[row] >= self.width:
            raise EllOverflow(f"in-degree of vertex {row} exceeds width {self.width}")
        col = int(self.fill[row])
        self.fill[row] += 1
        return col

    def writes_for(self, ops: Sequence[ResolvedOp]) -> list[EllWrite]:
        """Translate resolved slot ops into coalesced ELL cell writes.

        Raises :class:`EllOverflow` when an insert exceeds the fixed width;
        the index is then stale and must be rebuilt from the (already
        updated) host graph at a larger width.
        """
        writes: dict[tuple[int, int], EllWrite] = {}
        for (kind, slot, u, v, w) in ops:
            if kind == "delete":
                row, col = self.col_of.pop(slot)
                self.free.setdefault(row, []).append(col)
                writes[(row, col)] = EllWrite(row, col, self.v, 0.0)
            elif kind == "insert":
                col = self._alloc(v)
                self.col_of[slot] = (v, col)
                writes[(v, col)] = EllWrite(v, col, u, float(w))
            else:  # weight update in place
                row, col = self.col_of[slot]
                writes[(row, col)] = EllWrite(row, col, u, float(w))
        return list(writes.values())


@dataclasses.dataclass
class ShardWrite:
    """One sharded edge-cell assignment at linear index ``lin``
    (= shard · shard_capacity + position within the shard's cell range)."""

    lin: int
    src: int
    dst: int
    weight: float
    valid: bool


class ShardOverflow(Exception):
    """A destination shard ran out of edge cells — rebuild at a larger
    per-shard capacity (the index is stale once this is raised)."""


class ShardIndex:
    """Host mirror of the vertex-sharded edge layout (mesh ``data`` axis).

    Shard ``k`` of ``n`` owns the contiguous vertex block
    ``[k·V/n, (k+1)·V/n)`` and every edge whose DESTINATION falls in it, laid
    out in a fixed-capacity cell range ``[k·C, (k+1)·C)`` so a δE chunk
    becomes one device-side scatter into the owning shards (the engine's
    ``shard_map`` splits the ``[n·C]`` edge arrays along the cell axis).
    Plays the same role for the sharded COO view that :class:`EllIndex`
    plays for the ELL view; deletions keep the cell's endpoints (the VDC
    J-store identity-overwrite rule still needs the old destination) and
    recycle the cell through a per-shard free list.
    """

    def __init__(
        self, snap: GraphSnapshot, num_shards: int, *, min_capacity: int = 0
    ) -> None:
        v, n = snap.num_vertices, int(num_shards)
        if v % n:
            raise ValueError(f"num_vertices {v} not divisible by {n} shards")
        self.num_shards = n
        self.vertices_per_shard = v // n
        live = np.nonzero(snap.valid)[0]
        counts = np.bincount(
            snap.dst[live] // self.vertices_per_shard, minlength=n
        )
        # Inserts land where live edges do (the in-degree distribution), so
        # each shard gets room for the fullest shard's share of the free host
        # slots.  All shards together then hold (n · fullest / live) times
        # the host capacity: the layout's skew, not its shard count.
        full, free = int(counts.max(initial=0)), snap.capacity - live.size
        room = -(-free * full // live.size) if live.size else -(-free // n)
        cap = max(full + room, int(min_capacity), 8)
        self.shard_capacity = -(-cap // 8) * 8
        # cells fill in ascending slot order within each shard, like
        # EllIndex / to_ell
        shard = snap.dst[live].astype(np.int64) // self.vertices_per_shard
        rank = np.empty(live.size, dtype=np.int64)
        rank[np.argsort(shard, kind="stable")] = np.arange(live.size) - np.repeat(
            np.cumsum(counts) - counts, counts
        )
        # edge slot → linear cell index
        self.cell_of: dict[int, int] = dict(
            zip(live.tolist(), (shard * self.shard_capacity + rank).tolist())
        )
        self.dead: dict[int, tuple[int, int]] = {}  # freed cell → endpoints
        self.fill = counts.astype(np.int64)
        self.free: dict[int, list[int]] = {}

    def _alloc(self, shard: int) -> int:
        cells = self.free.get(shard)
        if cells:
            return cells.pop()
        if self.fill[shard] >= self.shard_capacity:
            raise ShardOverflow(
                f"shard {shard} edge cells exhausted at {self.shard_capacity}"
            )
        lin = shard * self.shard_capacity + int(self.fill[shard])
        self.fill[shard] += 1
        return lin

    def writes_for(self, ops: Sequence[ResolvedOp]) -> list[ShardWrite]:
        """Translate resolved slot ops into coalesced sharded-cell writes.

        Raises :class:`ShardOverflow` when an insert exceeds a shard's fixed
        capacity; the index is then stale and must be rebuilt from the
        (already updated) host graph.
        """
        writes: dict[int, ShardWrite] = {}
        for (kind, slot, u, v, w) in ops:
            if kind == "delete":
                lin = self.cell_of.pop(slot)
                self.free.setdefault(lin // self.shard_capacity, []).append(lin)
                self.dead[lin] = (u, v)
                writes[lin] = ShardWrite(lin, u, v, float(w), False)
            elif kind == "insert":
                lin = self._alloc(v // self.vertices_per_shard)
                self.cell_of[slot] = lin
                self.dead.pop(lin, None)
                writes[lin] = ShardWrite(lin, u, v, float(w), True)
            else:  # weight update in place
                lin = self.cell_of[slot]
                writes[lin] = ShardWrite(lin, u, v, float(w), True)
        return list(writes.values())

    def edge_arrays(
        self, snap: GraphSnapshot
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Sharded-layout COO arrays ``[n · shard_capacity]`` from a snapshot."""
        size = self.num_shards * self.shard_capacity
        src = np.zeros(size, dtype=np.int32)
        dst = np.zeros(size, dtype=np.int32)
        w = np.zeros(size, dtype=np.float32)
        valid = np.zeros(size, dtype=bool)
        slot = np.fromiter(self.cell_of.keys(), np.int64, len(self.cell_of))
        lin = np.fromiter(self.cell_of.values(), np.int64, len(self.cell_of))
        src[lin] = snap.src[slot]
        dst[lin] = snap.dst[slot]
        w[lin] = snap.weight[slot]
        valid[lin] = snap.valid[slot]
        # freed cells keep their last endpoints, matching the scatter path
        # (writes_for) and the unsharded snapshot: the VDC identity-overwrite
        # rule still needs a deleted edge's old destination to look dirty.
        for lin, (u, v) in self.dead.items():
            src[lin], dst[lin] = u, v
        return src, dst, w, valid


def product_graph(
    g: DynamicGraph | GraphSnapshot,
    nfa_delta: dict[int, list[tuple[int, int]]],
    num_states: int,
) -> tuple[int, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """RPQ product construction: vertex (v, q) with id ``v * num_states + q``.

    ``nfa_delta`` maps edge label → list of (q, q') NFA transitions.  Returns
    ``(num_product_vertices, src, dst, w, parent_edge_slot)`` COO arrays (one
    product edge per (graph edge, matching transition)).
    """
    live = np.nonzero(g.valid)[0]
    srcs, dsts, slots = [], [], []
    for e in live:
        for (q, q2) in nfa_delta.get(int(g.label[e]), ()):
            srcs.append(int(g.src[e]) * num_states + q)
            dsts.append(int(g.dst[e]) * num_states + q2)
            slots.append(int(e))
    n = g.num_vertices * num_states
    src = np.asarray(srcs, dtype=np.int32)
    dst = np.asarray(dsts, dtype=np.int32)
    w = np.ones(len(srcs), dtype=np.float32)
    return n, src, dst, w, np.asarray(slots, dtype=np.int32)
