"""The sweep's in-edge reductions in destination order equal the unsorted
segment ops bit for bit.

``repro.core.segments`` sorts the edge slots by destination once a sweep
and reduces each destination's run in blocks, carrying the run that
crosses a block's end.  Each case sets up a layout that the blocking can
get wrong; tiny blocks make the runs cross several of them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import segments

Q = 3


def _case(name: str, rng: np.random.Generator):
    """(src, dst, valid, num_local, block) for one layout."""
    v = 12
    if name == "empty_segments":  # most destinations have no in-edge
        e = 40
        dst = rng.choice([1, 5, 9], e)
        return rng.integers(0, v, e), dst, rng.random(e) < 0.8, v, 4
    if name == "all_invalid":
        e = 24
        return rng.integers(0, v, e), rng.integers(0, v, e), np.zeros(e, bool), v, 8
    if name == "long_run":  # one run of 29 live edges over blocks of 4
        e = 50
        dst = np.where(np.arange(e) < 29, 7, rng.integers(0, v, e))
        return rng.integers(0, v, e), rng.permutation(dst), np.ones(e, bool), v, 4
    if name == "ends_on_boundary":  # runs of exactly one block, and of two
        dst = np.repeat(np.arange(6), [4, 4, 8, 4, 1, 3])
        e = dst.size
        return rng.integers(0, v, e), rng.permutation(dst), np.ones(e, bool), v, 4
    if name == "foreign_ids":  # a vertex shard's view: foreign ids num_local
        e = 60
        dst = np.minimum(rng.integers(0, 2 * v, e), v)
        return rng.integers(0, 2 * v, e), dst, rng.random(e) < 0.9, v, 8
    if name == "one_block":  # fewer slots than a block
        e = 30
        return rng.integers(0, v, e), rng.integers(0, v, e), rng.random(e) < 0.7, v, 64
    raise KeyError(name)


CASES = ["empty_segments", "all_invalid", "long_run", "ends_on_boundary",
         "foreign_ids", "one_block"]


def _order(src, dst, valid, num_local, block, weight=None):
    weight = np.ones(len(src), np.float32) if weight is None else weight
    return segments.edge_order(
        jnp.asarray(src, jnp.int32),
        jnp.asarray(dst, jnp.int32),
        jnp.asarray(valid),
        jnp.asarray(weight),
        num_local,
        block=block,
    )


def _in_range(dst, valid, num_local):
    return jnp.asarray(valid & (dst < num_local))


@pytest.mark.parametrize("name", CASES)
@pytest.mark.parametrize("with_inf", [False, True], ids=["finite", "inf"])
def test_sorted_min_equals_segment_min(name, with_inf):
    rng = np.random.default_rng(CASES.index(name))
    src, dst, valid, v, block = _case(name, rng)
    n_src = int(src.max()) + 1
    state = rng.integers(0, 30, (Q, n_src)).astype(np.float32)
    if with_inf:
        state[rng.random(state.shape) < 0.5] = np.inf
    weight = rng.integers(1, 11, len(src)).astype(np.float32)
    state_j = jnp.asarray(state)

    msgs = state_j[:, src] + weight[None, :]
    keep = _in_range(dst, valid, v)
    want = jax.vmap(
        lambda m: jax.ops.segment_min(
            jnp.where(keep, m, jnp.inf), jnp.asarray(dst), num_segments=v
        )
    )(msgs)

    order = _order(src, dst, valid, v, block, weight)
    got = segments.sorted_segment_reduce(
        lambda s, w: state_j[:, s] + w[None, :], order, jnp.minimum, jnp.inf
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    degree = np.bincount(dst[np.asarray(keep)], minlength=v)[:v]
    np.testing.assert_array_equal(np.asarray(order.ends) >= 0, degree > 0)


@pytest.mark.parametrize("name", CASES)
@pytest.mark.parametrize("queries", [3, 40], ids=["one_word", "two_words"])
def test_sorted_or_equals_segment_max(name, queries):
    rng = np.random.default_rng(100 + CASES.index(name))
    src, dst, valid, v, block = _case(name, rng)
    n_src = int(src.max()) + 1
    changed = jnp.asarray(rng.random((queries, n_src)) < 0.3)

    keep = _in_range(dst, valid, v)
    hit = (changed[:, src] & keep[None, :]).astype(jnp.int32)
    want = jax.vmap(
        lambda h: jax.ops.segment_max(h, jnp.asarray(dst), num_segments=v)
    )(hit) > 0

    words = segments.pack_bits(changed)
    order = _order(src, dst, valid, v, block)
    got = segments.unpack_bits(
        segments.sorted_segment_reduce(
            lambda s, _: words[:, s], order, jnp.bitwise_or, 0
        ),
        queries,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("queries", [1, 16, 32, 33])
def test_pack_bits_round_trip(queries):
    mask = np.random.default_rng(queries).random((queries, 50)) < 0.5
    words = segments.pack_bits(jnp.asarray(mask))
    assert words.shape == (-(-queries // 32), 50) and words.dtype == jnp.uint32
    np.testing.assert_array_equal(
        np.asarray(segments.unpack_bits(words, queries)), mask
    )
