"""Bulk graph building equals the plain loops it replaced.

``powerlaw_graph`` draws endpoints from a CDF built once,
``update_stream`` finds the k-th present edge through a Fenwick tree, and
``DynamicGraph`` / ``ShardIndex`` load with numpy.  Each must give exactly
what the scalar loop below gives, for every seed.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core.graph import NO_LABEL, DynamicGraph, ShardIndex
from repro.data.graphgen import powerlaw_graph, split_90_10, update_stream


# ----------------------------------------------------------- reference loops
def powerlaw_loop(num_vertices, num_edges, *, seed=0, weighted=True, exponent=1.2,
                  num_labels=0):
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, num_vertices + 1, dtype=np.float64)
    probs = ranks ** (-exponent)
    probs /= probs.sum()
    perm = rng.permutation(num_vertices)
    seen, edges = set(), []
    while len(edges) < num_edges:
        u = int(perm[rng.choice(num_vertices, p=probs)])
        v = int(perm[rng.choice(num_vertices, p=probs)])
        if u == v or (u, v) in seen:
            continue
        seen.add((u, v))
        w = float(rng.integers(1, 11)) if weighted else 1.0
        if num_labels:
            edges.append((u, v, w, int(rng.integers(1, num_labels + 1))))
        else:
            edges.append((u, v, w))
    return edges


def update_stream_loop(existing, num_vertices, *, num_batches, batch_size=1,
                       delete_fraction=0.0, insert_pool=None, seed=0):
    rng = np.random.default_rng(seed)
    present = {(int(e[0]), int(e[1])): e for e in existing}
    pool = list(insert_pool or [])
    batches = []
    for _ in range(num_batches):
        batch = []
        for _ in range(batch_size):
            if present and rng.random() < delete_fraction:
                key = list(present)[int(rng.integers(len(present)))]
                e = present.pop(key)
                lbl = int(e[3]) if len(e) > 3 else 0
                batch.append((key[0], key[1], lbl, float(e[2]), -1))
            elif pool:
                e = pool.pop()
                key = (int(e[0]), int(e[1]))
                if key in present:
                    continue
                lbl = int(e[3]) if len(e) > 3 else 0
                present[key] = e
                batch.append((key[0], key[1], lbl, float(e[2]), +1))
            else:
                u, v = (int(x) for x in rng.integers(0, num_vertices, 2))
                if u == v or (u, v) in present:
                    continue
                w = float(rng.integers(1, 11))
                present[(u, v)] = (u, v, w)
                batch.append((u, v, 0, w, +1))
        if batch:
            batches.append(batch)
    return batches


def load_loop(g: DynamicGraph, edges, weighted: bool) -> None:
    """Slot-by-slot insertion into an empty graph of the same capacity."""
    for i, e in enumerate(edges):
        u, v = int(e[0]), int(e[1])
        lbl = int(e[3]) if len(e) > 3 else NO_LABEL
        g.src[i], g.dst[i] = u, v
        g.weight[i] = float(e[2]) if (weighted and len(e) > 2) else 1.0
        g.label[i] = lbl
        g.valid[i] = True
        g.out_degree[u] += 1
        g.in_degree[v] += 1
        g._slot[(u, v, lbl)] = i
    g._free = list(range(g.capacity - 1, len(edges) - 1, -1))


def shard_cells_loop(snap, num_shards: int, shard_capacity: int) -> dict[int, int]:
    vps = snap.num_vertices // num_shards
    fill = np.zeros(num_shards, np.int64)
    cell_of = {}
    for e in np.nonzero(snap.valid)[0]:
        sh = int(snap.dst[e]) // vps
        cell_of[int(e)] = sh * shard_capacity + int(fill[sh])
        fill[sh] += 1
    return cell_of


# ---------------------------------------------------------------------- tests
@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("v,e", [(3, 6), (8, 20), (64, 256), (256, 1024), (1000, 3000)])
@pytest.mark.parametrize(
    "kw",
    [{}, {"weighted": False}, {"num_labels": 4}, {"exponent": 0.5, "num_labels": 7}],
    ids=["weighted", "unweighted", "labelled", "flat-labelled"],
)
def test_powerlaw_graph_equals_scalar_draw_loop(v, e, kw, seed):
    assert powerlaw_graph(v, e, seed=seed, **kw) == powerlaw_loop(v, e, seed=seed, **kw)


@pytest.mark.parametrize(
    "kw",
    [
        dict(num_batches=20, batch_size=8, delete_fraction=0.5, pooled=True),
        dict(num_batches=30, batch_size=5, delete_fraction=0.3, pooled=False),
        dict(num_batches=40, batch_size=16, delete_fraction=0.9, pooled=True),
        dict(num_batches=10, batch_size=1, delete_fraction=0.0, pooled=True),
    ],
    ids=["pool", "fresh", "delete-heavy", "insert-only"],
)
def test_update_stream_equals_list_scan_loop(kw):
    kw = dict(kw)
    pooled = kw.pop("pooled")
    for seed in range(4):
        edges = powerlaw_graph(64, 256, seed=seed, num_labels=3 * (seed % 2))
        initial, pool = split_90_10(edges, seed=seed)
        if pooled:
            kw["insert_pool"] = pool[: 8 * seed + 5]  # runs dry mid-stream
        assert update_stream(initial, 64, seed=seed + 1, **kw) == update_stream_loop(
            initial, 64, seed=seed + 1, **kw
        )


@pytest.mark.parametrize("weighted", [True, False])
def test_dynamic_graph_bulk_load_equals_slot_loop(weighted):
    cases = [
        powerlaw_graph(64, 256, seed=1),
        powerlaw_graph(64, 200, seed=2, num_labels=3),
        [(0, 1, 2.5), (1, 2), (0, 1, 3.0), (2, 3, 1.0, 2), (0, 1, 7.0)],  # repeats
        np.array([[0, 1, 2.0], [3, 2, 1.5]]),
    ]
    for edges in cases:
        bulk = DynamicGraph(64, edges, capacity=600, weighted=weighted)
        ref = DynamicGraph(64, [], capacity=600, weighted=weighted)
        load_loop(ref, list(edges), weighted)
        (a, meta_a), (b, meta_b) = bulk.state_dict(), ref.state_dict()
        assert meta_a == meta_b
        for name in a:
            np.testing.assert_array_equal(a[name], b[name], err_msg=name)
            assert a[name].dtype == b[name].dtype
        assert list(bulk._slot.items()) == list(ref._slot.items())


def test_dynamic_graph_rejects_endpoints_outside_the_vertex_range():
    with pytest.raises(ValueError, match="outside"):
        DynamicGraph(4, [(0, 4, 1.0)])
    with pytest.raises(ValueError, match="outside"):
        DynamicGraph(4, [(-1, 2, 1.0)])


@pytest.mark.parametrize("num_shards", [2, 4, 8])
def test_shard_index_bulk_layout_equals_cell_loop(num_shards):
    for seed in range(3):
        edges = powerlaw_graph(64, 256, seed=seed)
        initial, pool = split_90_10(edges, seed=seed)
        g = DynamicGraph(64, initial, capacity=300)
        for batch in update_stream(initial, 64, num_batches=3, batch_size=8,
                                   delete_fraction=0.4, insert_pool=pool, seed=seed):
            g.apply_batch(batch)
        snap = g.snapshot()
        idx = ShardIndex(snap, num_shards)
        ref = shard_cells_loop(snap, num_shards, idx.shard_capacity)
        assert list(idx.cell_of.items()) == list(ref.items())
        src, dst, w, valid = idx.edge_arrays(snap)
        lin = np.fromiter(ref.values(), np.int64)
        slot = np.fromiter(ref.keys(), np.int64)
        np.testing.assert_array_equal(src[lin], snap.src[slot])
        np.testing.assert_array_equal(dst[lin], snap.dst[slot])
        np.testing.assert_array_equal(w[lin], snap.weight[slot])
        assert valid.sum() == valid[lin].sum() == snap.valid.sum()


@pytest.mark.parametrize("num_shards", [2, 4, 8])
def test_shard_index_cells_stay_near_host_capacity(num_shards):
    """Each shard gets room for the fullest shard's share of the free host
    slots, so all cells together are the layout's skew times the host
    capacity, also for a host graph sized 4x its edges (every free slot in
    every shard would be about 3x the capacity at 4 shards)."""
    v, e = 4096, 16384
    g = DynamicGraph(v, powerlaw_graph(v, e, seed=0), capacity=4 * e)
    idx = ShardIndex(g.snapshot(), num_shards)
    fullest = np.bincount(g.dst[g.valid] // (v // num_shards), minlength=num_shards).max()
    skew = num_shards * fullest / e
    total = num_shards * idx.shard_capacity
    assert g.capacity <= total <= skew * g.capacity + 8 * num_shards
    assert total <= 1.5 * g.capacity
