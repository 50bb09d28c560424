"""Cross-engine parity matrix for the vertex-sharded maintenance sweep.

One parameterized matrix sweeps ``backend × mode × drop.mode × shards``
(valid combos only: dropping composes with JOD, the ELL kernel realizes JOD)
and asserts bit-identical answers against the host ``SparseDiffIFE`` pointer
engine and SCRATCH on a random insert+delete stream.  This also closes two
pre-existing gaps: dropping × ELL and dropping × batched had no direct
coverage.

The ``shards=8`` column runs when 8 devices are visible — CI provides them
via ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — and a
subprocess smoke keeps the sharded path exercised in every plain test run.
A Hypothesis property test checks the sharded batched path (including the
ELL width-overflow re-trace, per-shard cell-overflow regrow, and diff-row
eviction paths) against unsharded sequential per-update maintenance.
"""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import dropping as dr
from repro.core import plan as qplan
from repro.core import queries as q
from repro.core.graph import DynamicGraph
from repro.core.scratch import scratch_like
from repro.core.session import CQPSession
from repro.core.sparse_engine import SparseDiffIFE
from repro.launch.mesh import make_data_mesh

V = 24
MAX_ITERS = 24
NDEV = jax.device_count()

needs8 = pytest.mark.skipif(
    NDEV < 8,
    reason="needs 8 devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)


def random_workload(seed: int, v: int = V, e: int = 96, num_batches: int = 4):
    """(initial edges, update batches) with insertion + deletion mixes."""
    rng = np.random.default_rng(seed)
    seen = {}
    while len(seen) < e:
        u, w = int(rng.integers(0, v)), int(rng.integers(0, v))
        if u != w:
            seen[(u, w)] = (u, w, float(rng.integers(1, 10)))
    edges = list(seen.values())
    initial, pool = edges[: e * 3 // 4], edges[e * 3 // 4 :]
    present = {(u, w) for (u, w, _x) in initial}
    batches = []
    for _ in range(num_batches):
        batch = []
        for _ in range(int(rng.integers(2, 5))):
            if present and rng.random() < 0.4:
                u, w = sorted(present)[int(rng.integers(0, len(present)))]
                batch.append((u, w, 0, 1.0, -1))
                present.discard((u, w))
            elif pool:
                u, w, x = pool.pop()
                batch.append((u, w, 0, x, +1))
                present.add((u, w))
        batches.append(batch)
    return initial, batches


DROPS = {
    "none": None,
    "det": dr.DropConfig(mode="det", selection="random", p=0.4, seed=7),
    "prob": dr.DropConfig(
        mode="prob", selection="random", p=0.4, seed=7, bloom_bits=1 << 12
    ),
}

# valid combos only: dropping needs JOD; the ELL kernel realizes JOD
MATRIX = [
    (backend, mode, dropmode)
    for backend in ("coo", "ell")
    for mode in ("jod", "vdc")
    for dropmode in ("none", "det", "prob")
    if not (mode == "vdc" and (dropmode != "none" or backend == "ell"))
]


def _make_engine(initial, backend, mode, dropmode, shards):
    mesh = make_data_mesh(shards) if shards > 1 else None
    kw = dict(mode=mode)
    if DROPS[dropmode] is not None:
        kw["drop"] = DROPS[dropmode]
    return q.sssp(
        DynamicGraph(V, initial, capacity=512),
        [0, V // 2],
        max_iters=MAX_ITERS,
        backend=backend,
        mesh=mesh,
        **kw,
    )


@pytest.mark.parametrize("shards", [1, pytest.param(8, marks=needs8)])
@pytest.mark.parametrize(
    "backend,mode,dropmode", MATRIX, ids=lambda m: str(m)
)
def test_parity_matrix(backend, mode, dropmode, shards):
    initial, batches = random_workload(seed=11)
    eng = _make_engine(initial, backend, mode, dropmode, shards)
    sparse = SparseDiffIFE(
        DynamicGraph(V, initial, capacity=512), [0, V // 2], max_iters=MAX_ITERS
    )
    scratch = scratch_like(
        eng.cfg, DynamicGraph(V, initial, capacity=512), eng.state.init
    )
    np.testing.assert_array_equal(eng.answers(), sparse.answers())
    np.testing.assert_array_equal(eng.answers(), scratch.answers())
    for batch in batches:
        eng.apply_updates(batch)
        sparse.apply_updates(batch)
        scratch.apply_updates(batch)
        np.testing.assert_array_equal(eng.answers(), sparse.answers())
        np.testing.assert_array_equal(eng.answers(), scratch.answers())


@pytest.mark.parametrize("shards", [1, pytest.param(8, marks=needs8)])
@pytest.mark.parametrize("engine", ["host", "scratch"])
@pytest.mark.parametrize(
    "backend,mode,dropmode", MATRIX, ids=lambda m: str(m)
)
def test_session_churn_engine_matrix(backend, mode, dropmode, shards, engine):
    """The parity matrix extended by an ENGINE axis, through the session
    facade and with query churn: a dense CQPSession in every (backend, mode,
    drop, shards) configuration must stay answer-identical to a host/scratch
    CQPSession across a stream that registers a query mid-stream and
    deregisters another (the dense engine initializes the new trace by
    in-engine recomputation; deregistration reclaims its diff rows)."""
    initial, batches = random_workload(seed=17, num_batches=3)
    drop = DROPS[dropmode]
    mesh = make_data_mesh(shards) if shards > 1 else None
    dense = CQPSession(
        DynamicGraph(V, initial, capacity=512),
        engine="dense",
        backend=backend,
        mode=mode,
        mesh=mesh,
        min_slots=2,
    )
    ref = CQPSession(DynamicGraph(V, initial, capacity=512), engine=engine)

    def dense_plan(src):
        return qplan.sssp(src, max_iters=MAX_ITERS, drop=drop)

    dh = dense.register_many([dense_plan(0), dense_plan(V // 2)])
    rh = ref.register_many(
        [
            qplan.sssp(0, max_iters=MAX_ITERS),
            qplan.sssp(V // 2, max_iters=MAX_ITERS),
        ]
    )

    def check():
        for a, b in zip(dh, rh):
            np.testing.assert_array_equal(dense.answers(a), ref.answers(b))

    check()
    for j, batch in enumerate(batches):
        dense.apply_updates(batch)
        ref.apply_updates(batch)
        check()
        if j == 0:  # mid-stream register (same family, new source)
            dh.append(dense.register(dense_plan(V // 3)))
            rh.append(ref.register(qplan.sssp(V // 3, max_iters=MAX_ITERS)))
            check()
        if j == 1:  # mid-stream deregister (oldest query retires)
            before = dense.nbytes()
            freed = dense.deregister(dh.pop(0))
            ref.deregister(rh.pop(0))
            assert freed >= 0 and dense.nbytes() <= before
            check()


@pytest.mark.parametrize("shards", [1, pytest.param(8, marks=needs8)])
@pytest.mark.parametrize("dropmode", ["det", "prob"])
def test_params_rewrite_midstream_parity(dropmode, shards):
    """Governor primitive through the session: rewriting a LIVE query's
    DropParams row mid-stream (escalate → shed, later de-escalate) must keep
    the sharded dense engine bit-identical to the unsharded one — the shed
    audit uses the stateless (seed, q, v, i) coin, so drop sets cannot
    depend on the mesh — and answers exactly equal to the host engine."""
    initial, batches = random_workload(seed=23, num_batches=3)
    drop_repr = DROPS[dropmode]
    escalate = dr.DropConfig(
        mode=dropmode, selection="degree", p=0.8, tau_min=6.0, seed=7,
        bloom_bits=1 << 12,
    )
    deescalate = dr.DropConfig(
        mode=dropmode, selection="random", p=0.2, seed=7, bloom_bits=1 << 12
    )

    def make(shards_):
        mesh = make_data_mesh(shards_) if shards_ > 1 else None
        s = CQPSession(
            DynamicGraph(V, initial, capacity=512),
            engine="dense",
            mesh=mesh,
            drop=drop_repr,
            min_slots=2,
        )
        hs = s.register_many(
            [
                qplan.sssp(0, max_iters=MAX_ITERS, drop=drop_repr),
                qplan.sssp(V // 2, max_iters=MAX_ITERS),
            ]
        )
        return s, hs

    a, ha = make(1)
    b, hb = make(shards)
    ref = CQPSession(DynamicGraph(V, initial, capacity=512), engine="host")
    rh = ref.register_many(
        [qplan.sssp(0, max_iters=MAX_ITERS), qplan.sssp(V // 2, max_iters=MAX_ITERS)]
    )

    def check():
        for x, y, r in zip(ha, hb, rh):
            np.testing.assert_array_equal(a.answers(x), b.answers(y))
            np.testing.assert_array_equal(a.answers(x), ref.answers(r))
        assert a.nbytes() == b.nbytes(), (a.nbytes(), b.nbytes())

    check()
    for j, batch in enumerate(batches):
        for s in (a, b):
            s.apply_updates(batch)
        ref.apply_updates(batch)
        check()
        if j == 0:  # escalate query 0 mid-stream: both sessions shed alike
            fa = a.set_drop_policy(ha[0], escalate)
            fb = b.set_drop_policy(hb[0], escalate)
            assert fa == fb >= 0, (fa, fb)
            check()
        if j == 1:  # de-escalate: survivors have audited coins — no reshed
            assert a.set_drop_policy(ha[0], deescalate) == b.set_drop_policy(
                hb[0], deescalate
            )
            check()


@pytest.mark.parametrize("dropmode", ["det", "prob"])
@pytest.mark.parametrize("backend", ["coo", "ell"])
def test_batched_dropping_parity(backend, dropmode):
    """Dropping × batched: the donated-buffer chunked stream must equal the
    per-update host path under both DroppedVT representations."""
    initial, batches = random_workload(seed=13)
    log = [u for b in batches for u in b]
    seq = _make_engine(initial, backend, "jod", dropmode, shards=1)
    bat = _make_engine(initial, backend, "jod", dropmode, shards=1)
    for u in log:
        seq.apply_updates([u])
    bat.apply_updates_batched(log, batch_size=4)
    np.testing.assert_array_equal(seq.answers(), bat.answers())


@needs8
@pytest.mark.parametrize("backend", ["coo", "ell"])
def test_sharded_batched_equals_unsharded_sequential_stream(backend):
    """Sharded batched ingestion == unsharded per-update ingestion, on a
    stream crafted to hit the growth paths: a hub vertex outruns both the
    fixed ELL width (re-trace) and its owner's shard cells (regrow)."""
    v = 16
    initial = [(i, (i + 1) % v, float(1 + i % 3)) for i in range(v)]
    hub = [(i, 3, 0, 1.0, +1) for i in range(v) if i != 3]  # in-degree 15
    rng = np.random.default_rng(3)
    mixed = [(int(rng.integers(0, v)), 7, 0, 2.0, +1) for _ in range(4)] + [
        (1, 2, 0, 1.0, -1),
        (3, 4, 0, 1.0, -1),
    ]
    log = hub + mixed
    kw = dict(
        max_iters=16,
        backend=backend,
        store_capacity=3,  # force diff-row evictions through the registry
        drop=dr.DropConfig(mode="det", selection="random", p=0.0),
    )
    seq = q.sssp(DynamicGraph(v, initial, capacity=64), [0, v // 2], **kw)
    bat = q.sssp(
        DynamicGraph(v, initial, capacity=64),
        [0, v // 2],
        mesh=make_data_mesh(8),
        **kw,
    )
    cells = bat._shard_index.shard_capacity
    for u in log:
        seq.apply_updates([u])
    bat.apply_updates_batched(log, batch_size=4)
    assert bat._shard_index.shard_capacity > cells  # the regrow happened
    np.testing.assert_array_equal(seq.answers(), bat.answers())


@needs8
def test_sharded_property_stream():
    """Hypothesis: sharded batched == unsharded sequential for arbitrary
    insert/delete streams."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    v = 16

    @st.composite
    def stream(draw):
        n_init = draw(st.integers(4, 20))
        mk = st.tuples(
            st.integers(0, v - 1), st.integers(0, v - 1), st.integers(1, 9)
        )
        edges = [
            (u, w, float(x))
            for (u, w, x) in draw(st.lists(mk, min_size=n_init, max_size=n_init))
            if u != w
        ]
        edges = list({(u, w): (u, w, x) for (u, w, x) in edges}.values())
        present = {(u, w) for (u, w, _x) in edges}
        ops = []
        for _ in range(draw(st.integers(1, 10))):
            if present and draw(st.booleans()):
                u, w = draw(st.sampled_from(sorted(present)))
                ops.append((u, w, 0, 1.0, -1))
                present.discard((u, w))
            else:
                u, w = draw(st.integers(0, v - 1)), draw(st.integers(0, v - 1))
                if u == w:
                    continue
                ops.append((u, w, 0, float(draw(st.integers(1, 9))), +1))
                present.add((u, w))
        return edges, ops

    @settings(max_examples=8, deadline=None)
    @given(wl=stream())
    def run(wl):
        edges, ops = wl
        seq = q.sssp(
            DynamicGraph(v, edges, capacity=96), [0, v // 2], max_iters=16
        )
        bat = q.sssp(
            DynamicGraph(v, edges, capacity=96),
            [0, v // 2],
            max_iters=16,
            mesh=make_data_mesh(8),
        )
        for u in ops:
            seq.apply_updates([u])
        bat.apply_updates_batched(ops, batch_size=4)
        np.testing.assert_array_equal(seq.answers(), bat.answers())

    run()


@needs8
def test_sharded_pagerank_and_wcc():
    """Non-SSSP query classes on the data mesh: WCC (min-label) stays
    bit-identical; PageRank's sum reductions reassociate across the sharded
    edge layout, so it carries float tolerance instead."""
    rng = np.random.default_rng(2)
    v = 16
    seen = {}
    while len(seen) < 48:
        u, w = int(rng.integers(0, v)), int(rng.integers(0, v))
        if u != w:
            seen[(u, w)] = (u, w, 1.0)
    edges = list(seen.values())
    log = [
        (int(rng.integers(0, v)), int(rng.integers(0, v)), 0, 1.0, s)
        for s in (+1, +1, -1, +1)
        for _ in range(2)
    ]
    log = [op for op in log if op[0] != op[1]]
    mesh = make_data_mesh(8)

    a = q.pagerank(DynamicGraph(v, edges, capacity=128), iters=8)
    b = q.pagerank(
        DynamicGraph(v, edges, capacity=128), iters=8, backend="ell", mesh=mesh
    )
    np.testing.assert_allclose(a.answers(), b.answers(), rtol=1e-6)
    a.apply_updates_batched(log, batch_size=4)
    b.apply_updates_batched(log, batch_size=4)
    np.testing.assert_allclose(a.answers(), b.answers(), rtol=1e-6)

    sym = [(u, w, 1.0) for (u, w, _x) in edges] + [
        (w, u, 1.0) for (u, w, _x) in edges
    ]
    c = q.wcc(DynamicGraph(v, sym, capacity=256), max_iters=16)
    d = q.wcc(DynamicGraph(v, sym, capacity=256), max_iters=16, mesh=mesh)
    np.testing.assert_array_equal(c.answers(), d.answers())


_SMOKE = textwrap.dedent(
    """
    import numpy as np, jax
    from jax.sharding import Mesh
    from repro.core import queries as q
    from repro.core.graph import DynamicGraph
    assert jax.device_count() == 8, jax.devices()
    mesh = Mesh(np.array(jax.devices()).reshape(8, 1), ("data", "model"))
    v = 16
    edges = [(i, (i + 1) % v, float(1 + i % 3)) for i in range(v)]
    edges += [(i, (i + 5) % v, 2.0) for i in range(0, v, 2)]
    a = q.sssp(DynamicGraph(v, edges, capacity=96), [0, 5], max_iters=16)
    b = q.sssp(DynamicGraph(v, edges, capacity=96), [0, 5], max_iters=16,
               mesh=mesh)
    np.testing.assert_array_equal(a.answers(), b.answers())
    log = [(2, 9, 0, 1.0, +1), (0, 1, 0, 1.0, -1), (4, 0, 0, 3.0, +1),
           (6, 7, 0, 1.0, -1)]
    sa = a.apply_updates_batched(log, batch_size=2)
    sb = b.apply_updates_batched(log, batch_size=2)
    np.testing.assert_array_equal(a.answers(), b.answers())
    assert sum(b.nbytes_per_device()) == b.nbytes() == a.nbytes()
    # the shards' counters sum to the one-device sweep's
    assert int(sb.scheduled) == int(sa.scheduled)
    assert float(sb.active_edges) == float(sa.active_edges) > 0
    print("SHARDED-SMOKE-OK")
    """
)


def test_sharded_parity_subprocess_smoke():
    """Always-on sharded coverage: re-exec under 8 emulated host devices so
    plain single-device test runs still drive the shard_map sweep."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + env.get(
        "PYTHONPATH", ""
    )
    out = subprocess.run(
        [sys.executable, "-c", _SMOKE],
        env=env,
        capture_output=True,
        text=True,
        timeout=560,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "SHARDED-SMOKE-OK" in out.stdout
