"""Per-destination reductions over the edge list in destination order.

The sweep reduces every vertex's in-edges twice an iteration: the messages
to their minimum (``cqp.expand``) and the frontier hits to their OR
(``cqp.frontier``).  As an unsorted ``segment_min``/``segment_max`` each is
one XLA scatter with an index per edge slot, which a TPU applies one index
at a time.  Here the live edges are sorted by destination once per sweep
(:func:`edge_order`), so each destination's in-edges form one contiguous
run, and :func:`sorted_segment_reduce` reduces the runs with shifted vector
ops and reads each run's value with a gather at its last edge.  Min and OR
are exact in any order, so the result equals the scatter's bit for bit.

The sorted edge axis is cut into blocks of ``L`` slots.  Only one block's
``[Q, L]`` values are live at a time; the partial of the run that crosses a
block's end is carried into the next block, so no ``[Q, E]`` buffer forms.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

Array = jnp.ndarray

# Edge slots per block: [16, 2^20] f32 is 64 MiB, an eighth of the [Q, E]
# message buffer the unsorted scatter reads at 2^23 edge slots.
EDGE_BLOCK = 1 << 20


class EdgeOrder(NamedTuple):
    """The edge slots sorted by destination, in ``K`` blocks of ``L``.

    Invalid and foreign slots (and the padding of the last block) sort
    after every destination's run, into one run of their own that nothing
    reads.
    """

    src: Array  # int32 [K, L] — source of each slot, destination order
    weight: Array  # f32 [K, L] — its effective weight
    start: Array  # bool [K, L] — the slot begins a run
    ends: Array  # int32 [num_local] — sorted position of each destination's
    # last in-edge; -1 for a destination with no live in-edge


def edge_order(
    src: Array,
    dst: Array,
    valid: Array,
    weight: Array,
    num_local: int,
    *,
    block: int = EDGE_BLOCK,
) -> EdgeOrder:
    """Sort the edge slots by destination.  ``dst`` holds local ids in
    ``[0, num_local]``: ``num_local`` (a vertex shard's foreign edges) and
    invalid slots belong to no run."""
    key = jnp.where(valid, dst, num_local).astype(jnp.int32)
    e = key.shape[0]
    # sort the keys with their slot ids and gather the payloads after: a
    # TPU compiles a two-operand unstable sort of 2^23 keys in a fraction
    # of the time it takes for one that carries them (any order within a
    # run reduces to the same min and OR)
    key, perm = jax.lax.sort(
        (key, jnp.arange(e, dtype=jnp.int32)), num_keys=1, is_stable=False
    )
    src, weight = src[perm], weight[perm]
    length = max(1, min(block, e))
    k = -(-e // length)
    pad = k * length - e
    key = jnp.pad(key, (0, pad), constant_values=num_local)
    src = jnp.pad(src, (0, pad))
    weight = jnp.pad(weight, (0, pad))
    # first sorted position of each destination, and where the runs end
    bounds = jnp.searchsorted(
        key, jnp.arange(num_local + 1, dtype=jnp.int32), side="left"
    ).astype(jnp.int32)
    start = key != jnp.pad(key[:-1], (1, 0), constant_values=-1)
    return EdgeOrder(
        src=src.reshape(k, length),
        weight=weight.reshape(k, length),
        start=start.reshape(k, length),
        ends=jnp.where(bounds[1:] > bounds[:-1], bounds[1:] - 1, -1),
    )


def sorted_segment_reduce(
    values: Callable[[Array, Array], Array],
    order: EdgeOrder,
    op: Callable[[Array, Array], Array],
    identity,
) -> Array:
    """Reduce each destination's run with ``op``: ``[Q, num_local]``.

    ``values(src, weight)`` forms one block's ``[Q, L]`` values from its
    ``[L]`` sources and weights.  ``op`` is associative and commutative
    (min, OR); ``identity`` is its identity and the value of a destination
    with no live in-edge.  Within a block, ``log2 L`` doubling steps leave at
    each slot the reduction of its run up to that slot: step ``s`` combines
    the slot ``s`` places earlier where that slot lies in the same run.
    """
    k, length = order.start.shape
    shape = jax.eval_shape(values, order.src[0], order.weight[0])
    q = shape.shape[0]
    slot = jnp.arange(length, dtype=jnp.int32)

    def block(b, c):
        out, tail = c
        row = lambda a: jax.lax.dynamic_index_in_dim(a, b, keepdims=False)  # noqa: E731
        x = values(row(order.src), row(order.weight))
        # the block slot where each slot's run begins; -1 where it began in
        # an earlier block
        first = jnp.where(row(order.start), slot, -1)
        s = 1
        while s < length:  # running max, by doubling
            first = jnp.maximum(first, jnp.pad(first[:-s], (s, 0), constant_values=-1))
            s *= 2
        s = 1
        while s < length:
            prev = jnp.pad(x[:, :-s], ((0, 0), (s, 0)), constant_values=identity)
            x = jnp.where((slot - first >= s)[None, :], op(x, prev), x)
            s *= 2
        # the run that crossed into this block from the last one
        x = jnp.where((first < 0)[None, :], op(x, tail[:, None]), x)
        pos = order.ends - b * length
        here = (order.ends >= 0) & (pos >= 0) & (pos < length)
        read = jnp.take(x, jnp.clip(pos, 0, length - 1), axis=1)
        return jnp.where(here[None, :], read, out), x[:, -1]

    out0 = jnp.full((q, order.ends.shape[0]), identity, shape.dtype)
    tail0 = jnp.full((q,), identity, shape.dtype)
    return jax.lax.fori_loop(0, k, block, (out0, tail0))[0]


def pack_bits(mask: Array) -> Array:
    """``[Q, N]`` bool → ``[ceil(Q / 32), N]`` uint32, row ``q`` in bit
    ``q % 32`` of word ``q // 32``: an OR over the rows' runs then moves one
    word a slot instead of ``Q`` flags."""
    q, n = mask.shape
    w = -(-q // 32)
    bits = jnp.pad(mask, ((0, w * 32 - q), (0, 0))).reshape(w, 32, n)
    shift = jnp.arange(32, dtype=jnp.uint32)[None, :, None]
    return (bits.astype(jnp.uint32) << shift).sum(axis=1, dtype=jnp.uint32)


def unpack_bits(words: Array, q: int) -> Array:
    """Inverse of :func:`pack_bits`: ``[W, N]`` uint32 → ``[q, N]`` bool."""
    shift = jnp.arange(32, dtype=jnp.uint32)[None, :, None]
    bits = (words[:, None, :] >> shift) & jnp.uint32(1)
    return bits.reshape(-1, words.shape[-1])[:q].astype(bool)
