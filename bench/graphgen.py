"""The benchmark's own graph and δE stream, drawn from the seed.

``powerlaw_edges`` keeps the shape of the program's ``powerlaw_graph``:
both endpoints follow a Zipf law over a permuted vertex order, with no
self-loops, no repeated ``(u, v)`` and integer weights 1–10.  It draws in
bulk with NumPy, so 2^23 edges take seconds.

``EdgeStream`` follows the paper's §6.1 split: shuffle, load 90%, hold 10%
out as the insert pool.  Which edges are loaded and which order they load
and enter in are drawn from two streams, so that one graph and one split
can be served in many orders.  Each update deletes a live edge (with the traffic's
deletion share) or inserts an edge of the original set that is not live:
from the pool first, then a deleted one again; with every edge live, it
deletes.  The stream never ends, and the live edges never outnumber the
original set.  Its live set is the
harness's own record of the graph, which the reference reads.
"""

from __future__ import annotations

import numpy as np


def powerlaw_edges(
    num_vertices: int, num_edges: int, exponent: float, rng: np.random.Generator
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``(src, dst, weight)`` of the first ``num_edges`` distinct directed
    pairs drawn."""
    if num_edges > num_vertices * (num_vertices - 1):
        raise ValueError("more edges than distinct vertex pairs")
    draw = _ZipfDraw(num_vertices, exponent)
    perm = rng.permutation(num_vertices).astype(np.int64)
    have = np.empty(0, np.int64)  # sorted keys drawn so far
    parts = []
    missing = num_edges
    while missing:
        # the Zipf head repeats often, so draw several candidates per edge
        n = max(4 * missing, 1 << 16)
        u = perm[draw(rng.random(n))]
        v = perm[draw(rng.random(n))]
        cand = (u * num_vertices + v)[u != v]
        new = np.unique(cand)
        if have.size:
            pos = np.searchsorted(have, new).clip(max=have.size - 1)
            new = new[have[pos] != new]
        if new.size > missing:
            # the last round keeps the first distinct candidates it drew
            cand = cand[np.isin(cand, new)]
            _, first = np.unique(cand, return_index=True)
            new = cand[np.sort(first)[:missing]]
        parts.append(new)
        have = np.union1d(have, new)
        missing -= new.size
    keys = np.concatenate(parts)
    weight = rng.integers(1, 11, num_edges).astype(np.float32)
    return (
        (keys // num_vertices).astype(np.int32),
        (keys % num_vertices).astype(np.int32),
        weight,
    )


class _ZipfDraw:
    """Rank of each uniform draw under P(rank k) ∝ (k + 1)^-exponent, equal
    to ``searchsorted(cdf, r, side="right")``: a table of buckets settles
    most draws, and a search settles those whose bucket spans ranks."""

    BUCKETS = 1 << 24

    def __init__(self, num_vertices: int, exponent: float) -> None:
        probs = np.arange(1, num_vertices + 1, dtype=np.float64) ** (-exponent)
        self.cdf = np.cumsum(probs)
        self.cdf /= self.cdf[-1]
        self.last = num_vertices - 1
        edges = np.arange(self.BUCKETS + 1, dtype=np.float64) / self.BUCKETS
        bound = np.searchsorted(self.cdf, edges, side="right").clip(max=self.last)
        # the bucket's one rank, or -1 where the bucket spans several
        self.table = np.where(bound[:-1] == bound[1:], bound[:-1], -1).astype(np.int32)

    def __call__(self, r: np.ndarray) -> np.ndarray:
        rank = self.table[(r * self.BUCKETS).astype(np.int64)]
        span = np.flatnonzero(rank < 0)
        rank[span] = np.searchsorted(self.cdf, r[span], side="right").clip(max=self.last)
        return rank


class EdgeStream:
    """Live set and endless update stream over one original edge set.

    Positions in ``order`` hold edge ids in three runs: live ``[0, L)``,
    deleted ``[L, L + D)`` and never-inserted pool ``[L + D, N)``, so every
    update is an O(1) swap.
    """

    def __init__(
        self,
        src: np.ndarray,
        dst: np.ndarray,
        weight: np.ndarray,
        *,
        load_share: float,
        split: np.random.Generator,
        order: np.random.Generator,
    ) -> None:
        """``split`` draws which edges are loaded, ``order`` the order in
        which the loaded edges load and the pool's edges are inserted."""
        self.src, self.dst, self.weight = src, dst, weight
        n = src.size
        self.num_live = int(n * load_share)
        part = split.permutation(n).astype(np.int64)
        self.order = np.concatenate([order.permutation(part[: self.num_live]),
                                     order.permutation(part[self.num_live:])])
        self.pos = np.empty(n, np.int64)
        self.pos[self.order] = np.arange(n)
        self.num_deleted = 0
        self.live = np.zeros(n, bool)
        self.live[self.order[: self.num_live]] = True

    @property
    def num_edges(self) -> int:
        return int(self.src.size)

    def loaded(self) -> np.ndarray:
        """Ids of the edges live before any update, in load order."""
        return self.order[: self.num_live].copy()

    def _swap(self, i: int, j: int) -> None:
        a, b = self.order[i], self.order[j]
        self.order[i], self.order[j] = b, a
        self.pos[a], self.pos[b] = j, i

    def take(self, count: int, delete_share: float, rng: np.random.Generator) -> list:
        """The next ``count`` updates as ``(u, v, label, w, ±1)`` tuples,
        applied to the live set."""
        coin = rng.random(count)
        pick = rng.random(count)
        out = []
        for c, r in zip(coin.tolist(), pick.tolist()):
            live, dead = self.num_live, self.num_deleted
            # with every edge live, only a delete is possible
            if live and (c < delete_share or live == self.num_edges):
                self._swap(int(r * live), live - 1)
                e = int(self.order[live - 1])
                self.num_live -= 1
                self.num_deleted += 1
                self.live[e] = False
                sign = -1
            else:
                if live + dead < self.num_edges:
                    # the pool's first edge moves to the front of the deleted run
                    self._swap(live + dead, live)
                else:
                    self._swap(live + int(r * dead), live)
                    self.num_deleted -= 1
                e = int(self.order[live])
                self.num_live += 1
                self.live[e] = True
                sign = 1
            out.append((int(self.src[e]), int(self.dst[e]), 0, float(self.weight[e]), sign))
        return out
