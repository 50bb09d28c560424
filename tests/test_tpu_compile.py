"""Ahead-of-time compiles of the served sweep for a TPU v5e, at real widths.

The chip smoke's deployment (``chip_smoke.py``): Q=16 SSSP slots, V=2^20
vertices, 2^23 edge slots, δE chunks of B=1024; and the benchmark cells'
(``bench/configs/lj_sssp.json``): V=589,824 at the same Q, slots and B.
Each program is compiled for a described v5e that is not attached, so the
TPU compiler refuses here what it would refuse on the chip, and
``memory_analysis()`` must fit one chip's 16 GB of HBM.  Nothing runs; this
says nothing of results or times.
"""

from __future__ import annotations

import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.core import engine as eng
from repro.core import plan

Q, V, E, B = 16, 1 << 20, 1 << 23, 1024
V_BENCH = 589_824  # the benchmark cells' vertices
HBM_BYTES = 16 * 10**9  # TPU v5e, Google Cloud documentation "TPU v5e"
# temporaries of the chunk program at the benchmark cells' shape when the
# sweep reduced in-edges with unsorted segment_min/segment_max scatters
# (v5e compile analysis): the destination-ordered reduction holds no more
SCATTER_SWEEP_TEMP_BYTES = 2_934_946_816


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def cfg():
    p = plan.sssp(0, max_iters=64)
    return eng.EngineConfig(
        num_queries=Q, num_vertices=V, max_iters=p.max_iters, semiring=p.semiring
    )


def _shapes(cfg, num_edges):
    """(state, graph, update chunk, dirty) as shape/dtype structs."""
    v = cfg.num_vertices
    state = jax.eval_shape(
        lambda: eng.make_state(cfg, jnp.zeros((Q, v), jnp.float32), num_edges)
    )
    e = lambda dt: jax.ShapeDtypeStruct((num_edges,), dt)  # noqa: E731
    vtx = jax.ShapeDtypeStruct((v,), jnp.int32)
    g = eng.GraphArrays(
        e(jnp.int32), e(jnp.int32), e(jnp.float32), e(jnp.bool_), vtx, vtx
    )
    dtypes = {"weight": jnp.float32, "valid": jnp.bool_, "ell_w": jnp.float32}
    upd = eng.UpdateBatch(
        *(jax.ShapeDtypeStruct((B,), dtypes.get(f, jnp.int32))
          for f in eng.UpdateBatch._fields)
    )
    dirty = jax.ShapeDtypeStruct((Q, v), jnp.bool_)
    return state, g, upd, dirty


def _placed(tree, shardings):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tree,
        shardings,
    )


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes
        + m.output_size_in_bytes
        + m.temp_size_in_bytes
        - m.alias_size_in_bytes
    )


def _one_chip(topo, tree):
    one = SingleDeviceSharding(topo.devices[0])
    return _placed(tree, jax.tree.map(lambda _: one, tree))


def test_batched_step_compiles_for_v5e_and_fits(topo, cfg):
    """The donated per-chunk program ``DiffIFE`` runs for every δE chunk."""
    state, g, upd, _ = _one_chip(topo, _shapes(cfg, E))
    compiled = (
        jax.jit(partial(eng.batched_step, cfg), donate_argnums=(0, 1))
        .lower(state, g, upd)
        .compile()
    )
    assert _device_bytes(compiled) < HBM_BYTES, compiled.memory_analysis()
    assert "tpu_custom_call" not in compiled.as_text()  # pure XLA, no Pallas


def _scatter_indices(text: str, *, in_loops: bool) -> list[int]:
    """Index count of every ``scatter`` in compiled HLO text: in the
    computations a ``while`` body reaches, or in all of them."""
    comps: dict[str, list[str]] = {}
    name = None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    shapes = {}
    for lines in comps.values():
        for line in lines:
            d = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]", line)
            if d:
                shapes[d.group(1)] = [int(x) for x in d.group(2).split(",") if x]
    seen, todo = set(), [] if in_loops else list(comps)
    for lines in comps.values():
        for line in lines:
            todo += re.findall(r"body=%([\w.\-]+)", line)
    counts = []
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            todo += re.findall(r"(?:calls|body|condition|to_apply)=%([\w.\-]+)", line)
            m = re.search(r" scatter\((%[\w.\-]+), (%[\w.\-]+)", line)
            if m:
                dims = shapes.get(m.group(2)[1:], [0])
                counts.append(max(dims))
    return counts


def test_bench_chunk_program_reduces_in_edges_without_scatter(topo):
    """The chunk program at the benchmark cells' shape: the sweep reduces
    each vertex's in-edges in destination order, so its loop holds no
    scatter with an index per edge slot (a TPU applies one index at a
    time), and its temporaries stay within the scatter sweep's."""
    p = plan.sssp(0, max_iters=64)
    cfg = eng.EngineConfig(
        num_queries=Q, num_vertices=V_BENCH, max_iters=p.max_iters,
        semiring=p.semiring,
    )
    state, g, upd, _ = _one_chip(topo, _shapes(cfg, E))
    compiled = (
        jax.jit(partial(eng.batched_step, cfg), donate_argnums=(0, 1))
        .lower(state, g, upd)
        .compile()
    )
    text = compiled.as_text()
    # the degree refresh before the loop scatters an index per edge slot
    assert max(_scatter_indices(text, in_loops=False)) == E
    assert all(n < E for n in _scatter_indices(text, in_loops=True))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= SCATTER_SWEEP_TEMP_BYTES, temp


def test_maintain_compiles_for_v5e_and_fits(topo, cfg):
    """The register-time sweep: not donated, so the old and the new state
    are both live."""
    state, g, _, dirty = _one_chip(topo, _shapes(cfg, E))
    compiled = jax.jit(partial(eng.maintain, cfg)).lower(state, g, dirty).compile()
    assert _device_bytes(compiled) < HBM_BYTES, compiled.memory_analysis()


def test_sharded_batched_step_compiles_for_4_v5e_chips_and_fits(topo, cfg):
    """The vertex-sharded chunk program on a 4-chip ``data`` mesh: every
    per-vertex carry is split four ways, so each chip holds a quarter."""
    mesh = Mesh(np.array(topo.devices[:4]).reshape(4, 1), ("data", "model"))
    state, g, upd, _ = _shapes(cfg, E)
    named = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
    specs = (
        eng._state_pspecs(state),
        eng._graph_pspecs(g),
        eng.UpdateBatch(*([jax.sharding.PartitionSpec()] * len(upd))),
    )
    args = [
        _placed(t, jax.tree.map(named, s, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))
        for t, s in zip((state, g, upd), specs)
    ]
    compiled = (
        jax.jit(partial(eng.batched_step_sharded, cfg, mesh), donate_argnums=(0, 1))
        .lower(*args)
        .compile()
    )
    per_chip = _device_bytes(compiled)
    assert per_chip < HBM_BYTES, compiled.memory_analysis()
    text = compiled.as_text()
    assert "all-gather" in text  # the exact front crosses chips each iteration
