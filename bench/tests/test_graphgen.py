"""The benchmark's generator and stream at small size."""

from __future__ import annotations

import numpy as np
import pytest

from bench.graphgen import EdgeStream, _ZipfDraw, powerlaw_edges


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 11])
def test_powerlaw_edges_shape(seed):
    v, e = 1 << 10, 1 << 13
    src, dst, w = powerlaw_edges(v, e, 1.2, np.random.default_rng(seed))
    keys = src.astype(np.int64) * v + dst
    assert src.size == e
    assert np.unique(keys).size == e  # no duplicate (u, v)
    assert (src != dst).all()  # no self-loops
    assert ((w >= 1) & (w <= 10) & (w == np.round(w))).all()
    assert set(np.unique(w).tolist()) == set(range(1, 11))
    # a Zipf head: the busiest endpoint carries far more than the mean
    deg = np.bincount(src, minlength=v)
    assert deg.max() > 20 * deg.mean()


def test_powerlaw_edges_repeat_for_a_seed():
    a = powerlaw_edges(512, 2048, 1.2, np.random.default_rng([5, 0]))
    b = powerlaw_edges(512, 2048, 1.2, np.random.default_rng([5, 0]))
    c = powerlaw_edges(512, 2048, 1.2, np.random.default_rng([6, 0]))
    assert all((x == y).all() for x, y in zip(a, b))
    assert not (a[0] == c[0]).all()


def test_zipf_draw_equals_a_search_of_the_cdf():
    draw = _ZipfDraw(1 << 12, 1.2)
    r = np.random.default_rng(0).random(1 << 16)
    want = np.searchsorted(draw.cdf, r, side="right").clip(max=(1 << 12) - 1)
    assert (draw(r) == want).all()


def _stream(n=400, load=0.9, order=4):
    v = 64
    src, dst, w = powerlaw_edges(v, n, 1.2, np.random.default_rng(1))
    return EdgeStream(src, dst, w, load_share=load, split=np.random.default_rng(2),
                      order=np.random.default_rng(order))


def test_stream_keeps_its_live_set_and_never_exceeds_the_original_set():
    s = _stream()
    n = s.num_edges
    live = {(int(a), int(b)) for a, b in zip(s.src[s.live], s.dst[s.live])}
    pool = set(s.order[s.num_live:].tolist())
    assert len(live) == int(n * 0.9) and len(pool) == n - len(live)
    rng = np.random.default_rng(3)
    inserted_from_pool = 0
    for _ in range(60):
        for (u, v, _lbl, w, sign) in s.take(32, 0.1, rng):
            if sign < 0:
                assert (u, v) in live  # deletes hit live edges
                live.remove((u, v))
            else:
                assert (u, v) not in live  # inserts add edges that are not live
                live.add((u, v))
                e = next(i for i in range(n) if s.src[i] == u and s.dst[i] == v)
                inserted_from_pool += e in pool
                pool.discard(e)
                assert 1 <= w <= 10
            assert len(live) <= n
        assert live == {(int(a), int(b)) for a, b in zip(s.src[s.live], s.dst[s.live])}
        assert s.num_live == len(live) == int(s.live.sum())
    # the pool ran out long ago: inserts came from it first, then re-inserts
    assert inserted_from_pool == n - int(n * 0.9) and not pool


def test_stream_deletes_when_every_edge_is_live():
    s = _stream(n=50, load=1.0)
    ups = s.take(5, 0.0, np.random.default_rng(0))
    assert ups[0][4] == -1 and s.num_live <= 50


def test_split_is_drawn_apart_from_the_order():
    a, b = _stream(order=4), _stream(order=5)
    assert (a.live == b.live).all()  # the same edges loaded
    assert not (a.loaded() == b.loaded()).all()  # in another order
    assert set(a.order[a.num_live:].tolist()) == set(b.order[b.num_live:].tolist())
