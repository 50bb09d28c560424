"""Runtime query lifecycle: CQPSession register/deregister properties.

The session contract (DESIGN.md §9), asserted across all three engines and
(for the dense engine) sharded and unsharded:

* **register-convergence** — registering a plan mid-stream converges to
  exactly the answers of a session that had the plan from the start (the
  dense engine initializes the trace by in-engine recomputation; min-family
  fixpoints are unique, so WHEN a query registers can never change WHAT it
  answers).
* **deregister-monotonicity** — every deregistration monotonically reduces
  ``nbytes()`` (diff rows are zeroed and accounted bytes returned).
* **slot-pool mechanics** — geometric regrow past ``min_slots``, slot reuse
  after deregistration, per-query drop policies, family validation.

A Hypothesis property test generalizes the convergence check to arbitrary
insert/delete streams with a random registration point.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import dropping as dr
from repro.core import plan as qplan
from repro.core.graph import DynamicGraph
from repro.core.session import ENGINES, CQPSession
from repro.launch.mesh import make_data_mesh

V = 16
MAX_ITERS = 16
NDEV = jax.device_count()

needs8 = pytest.mark.skipif(
    NDEV < 8,
    reason="needs 8 devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)

SHARD_AXIS = [1, pytest.param(8, marks=needs8)]


def workload(seed: int = 5):
    rng = np.random.default_rng(seed)
    seen = {}
    while len(seen) < 40:
        u, w = int(rng.integers(0, V)), int(rng.integers(0, V))
        if u != w:
            seen[(u, w)] = (u, w, float(rng.integers(1, 9)))
    edges = list(seen.values())
    initial, pool = edges[:30], edges[30:]
    present = {(u, w) for (u, w, _x) in initial}
    log = []
    for _ in range(12):
        if present and rng.random() < 0.35:
            u, w = sorted(present)[int(rng.integers(0, len(present)))]
            log.append((u, w, 0, 1.0, -1))
            present.discard((u, w))
        elif pool:
            u, w, x = pool.pop()
            log.append((u, w, 0, x, +1))
            present.add((u, w))
    return initial, log


def _graph(initial):
    return DynamicGraph(V, initial, capacity=256)


def _session(initial, engine, shards=1, **kw):
    mesh = make_data_mesh(shards) if shards > 1 else None
    return CQPSession(_graph(initial), engine=engine, mesh=mesh, **kw)


def _shards_for(engine):
    # the sharded sweep is dense-only; host/scratch run unsharded
    return [1, 8] if engine == "dense" and NDEV >= 8 else [1]


@pytest.mark.parametrize("engine", ENGINES)
def test_register_midstream_converges(engine):
    """register(plan) mid-stream == constructing with the plan from start."""
    initial, log = workload()
    plans = [qplan.sssp(0, max_iters=MAX_ITERS), qplan.sssp(7, max_iters=MAX_ITERS)]
    for shards in _shards_for(engine):
        a = _session(initial, engine, shards)
        ha = a.register_many(plans)
        b = _session(initial, engine, shards)
        hb0 = b.register(plans[0])
        a.apply_updates(log[:6])
        b.apply_updates(log[:6])
        hb1 = b.register(plans[1])  # mid-stream
        a.apply_updates(log[6:])
        b.apply_updates(log[6:])
        np.testing.assert_array_equal(a.answers(ha[0]), b.answers(hb0))
        np.testing.assert_array_equal(a.answers(ha[1]), b.answers(hb1))


@pytest.mark.parametrize("engine", ENGINES)
def test_deregister_monotonically_reduces_nbytes(engine):
    initial, log = workload(seed=9)
    for shards in _shards_for(engine):
        s = _session(initial, engine, shards)
        handles = s.register_many(
            [qplan.sssp(i, max_iters=MAX_ITERS) for i in range(4)]
        )
        s.apply_updates(log)
        sizes = [s.nbytes()]
        for h in handles:
            freed = s.deregister(h)
            assert freed >= 0
            sizes.append(s.nbytes())
        assert all(b <= a for a, b in zip(sizes, sizes[1:])), sizes
        assert sizes[-1] == 0  # no registered queries → no accounted diffs
        assert s.bytes_freed_total == sizes[0] - sizes[-1]


@pytest.mark.parametrize("shards", SHARD_AXIS)
def test_dense_slot_pool_regrow_and_reuse(shards):
    """min_slots=1 → geometric regrow to 8 slots for 5 queries; a freed slot
    is reused by the next registration and answers stay correct."""
    initial, log = workload(seed=11)
    s = _session(initial, "dense", shards, min_slots=1)
    handles = [s.register(qplan.sssp(i, max_iters=MAX_ITERS)) for i in range(5)]
    assert s.stats()["slot_capacity"] == 8
    s.apply_updates_batched(log, batch_size=4)
    s.deregister(handles[2])
    h_new = s.register(qplan.sssp(9, max_iters=MAX_ITERS))
    assert s.stats()["slot_capacity"] == 8  # reused the freed slot
    ref = _session(initial, "host")
    rh = ref.register(qplan.sssp(9, max_iters=MAX_ITERS))
    ref.apply_updates(log)
    np.testing.assert_array_equal(s.answers(h_new), ref.answers(rh))
    # survivors unaffected by the churn
    ref0 = ref.register(qplan.sssp(0, max_iters=MAX_ITERS))
    np.testing.assert_array_equal(s.answers(handles[0]), ref.answers(ref0))


def test_per_query_drop_policies_stay_exact():
    """Each query brings its own §5 selection policy; answers stay exact and
    the heavier-dropping query stores fewer diffs."""
    initial, log = workload(seed=13)
    s = _session(initial, "dense", drop=dr.DropConfig(mode="det"))
    h_heavy = s.register(
        qplan.sssp(
            0,
            max_iters=MAX_ITERS,
            drop=dr.DropConfig(mode="det", selection="random", p=0.9, seed=3),
        )
    )
    h_none = s.register(qplan.sssp(0, max_iters=MAX_ITERS))  # same query, no drops
    s.apply_updates_batched(log, batch_size=4)
    np.testing.assert_array_equal(s.answers(h_heavy), s.answers(h_none))
    slot_heavy = s._handles[h_heavy.qid]
    slot_none = s._handles[h_none.qid]
    impl = s._impl.impl
    assert impl.slot_nbytes(slot_heavy) < impl.slot_nbytes(slot_none)


def test_lifecycle_validation():
    initial, _ = workload()
    s = _session(initial, "dense")
    h = s.register(qplan.sssp(0, max_iters=MAX_ITERS))
    with pytest.raises(ValueError, match="family"):
        s.register(qplan.khop(1, k=4))
    with pytest.raises(ValueError, match="drop mode"):
        s.register(
            qplan.sssp(
                1, max_iters=MAX_ITERS, drop=dr.DropConfig(mode="det", p=0.5)
            )
        )
    s.deregister(h)
    with pytest.raises(ValueError, match="not registered"):
        s.deregister(h)
    with pytest.raises(ValueError, match="mesh"):
        CQPSession(_graph(initial), engine="host", mesh=object())


def test_failed_register_batch_leaves_session_untouched():
    """A rejected opening batch must not half-commit the family: the session
    still accepts a clean batch afterwards, and pre-engine updates keep
    landing on the base graph (not a phantom product space)."""
    initial, log = workload()
    s = _session(initial, "dense")
    nfa = qplan.NFA.star(1)
    with pytest.raises(ValueError, match="family"):
        s.register_many(
            [qplan.rpq(0, nfa, max_iters=MAX_ITERS), qplan.sssp(1, max_iters=MAX_ITERS)]
        )
    assert s.num_queries == 0
    s.apply_updates(log[:2])  # pre-engine: applies to the base graph
    h = s.register(qplan.sssp(0, max_iters=MAX_ITERS))  # non-NFA family works
    s.apply_updates(log[2:])
    ref = _session(initial, "host")
    rh = ref.register(qplan.sssp(0, max_iters=MAX_ITERS))
    ref.apply_updates(log)
    np.testing.assert_array_equal(s.answers(h), ref.answers(rh))

    # mixed DroppedVT representations in one batch are rejected up front,
    # and the session stays open for a clean retry
    s2 = _session(initial, "dense")
    with pytest.raises(ValueError, match="drop mode"):
        s2.register_many(
            [
                qplan.sssp(0, max_iters=MAX_ITERS, drop=dr.DropConfig(mode="det", p=0.5)),
                qplan.sssp(1, max_iters=MAX_ITERS, drop=dr.DropConfig(mode="prob", p=0.5)),
            ]
        )
    assert s2.num_queries == 0
    s2.register(qplan.sssp(0, max_iters=MAX_ITERS, drop=dr.DropConfig(mode="prob", p=0.5)))

    # an engine that cannot run the family rolls the whole commit back
    s3 = _session(initial, "host")
    with pytest.raises(ValueError, match="min-family"):
        s3.register(qplan.pagerank())
    h3 = s3.register(qplan.sssp(0, max_iters=MAX_ITERS))  # not bricked
    assert s3.answers(h3).shape == (V,)


def test_rpq_session_churn():
    """RPQ plans (NFA product) through the session lifecycle."""
    edges = [(i, (i + 1) % V, 1.0, 1 + (i % 2)) for i in range(V)]
    nfa = qplan.NFA.concat_star(1, 2)
    s = CQPSession(DynamicGraph(V, edges, capacity=128), engine="dense")
    h0 = s.register(qplan.rpq(0, nfa, max_iters=MAX_ITERS))
    s.apply_updates([(0, 5, 1, 1.0, +1)])
    h1 = s.register(qplan.rpq(4, nfa, max_iters=MAX_ITERS))  # mid-stream
    ref = CQPSession(DynamicGraph(V, edges, capacity=128), engine="dense")
    r0 = ref.register(qplan.rpq(0, nfa, max_iters=MAX_ITERS))
    r1 = ref.register(qplan.rpq(4, nfa, max_iters=MAX_ITERS))
    ref.apply_updates([(0, 5, 1, 1.0, +1)])
    np.testing.assert_array_equal(s.reachable(h0), ref.reachable(r0))
    np.testing.assert_array_equal(s.reachable(h1), ref.reachable(r1))
    assert s.deregister(h0) >= 0


def test_property_midstream_register_equals_from_start():
    """Hypothesis: for arbitrary insert/delete streams and a random split
    point, mid-stream registration converges to from-start answers on every
    engine (dense checked against host for cross-engine parity too)."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @st.composite
    def stream(draw):
        mk = st.tuples(
            st.integers(0, V - 1), st.integers(0, V - 1), st.integers(1, 9)
        )
        edges = [
            (u, w, float(x))
            for (u, w, x) in draw(st.lists(mk, min_size=6, max_size=24))
            if u != w
        ]
        edges = list({(u, w): (u, w, x) for (u, w, x) in edges}.values())
        present = {(u, w) for (u, w, _x) in edges}
        ops = []
        for _ in range(draw(st.integers(2, 10))):
            if present and draw(st.booleans()):
                u, w = draw(st.sampled_from(sorted(present)))
                ops.append((u, w, 0, 1.0, -1))
                present.discard((u, w))
            else:
                u, w = draw(st.integers(0, V - 1)), draw(st.integers(0, V - 1))
                if u == w:
                    continue
                ops.append((u, w, 0, float(draw(st.integers(1, 9))), +1))
                present.add((u, w))
        cut = draw(st.integers(0, len(ops)))
        src = draw(st.integers(0, V - 1))
        return edges, ops, cut, src

    @settings(max_examples=10, deadline=None)
    @given(wl=stream())
    def run(wl):
        edges, ops, cut, src = wl
        rows = {}
        for engine in ENGINES:
            a = CQPSession(DynamicGraph(V, edges, capacity=256), engine=engine)
            ha = a.register(qplan.sssp(src, max_iters=MAX_ITERS))
            a.apply_updates(ops)
            b = CQPSession(DynamicGraph(V, edges, capacity=256), engine=engine)
            b.apply_updates(ops[:cut])
            hb = b.register(qplan.sssp(src, max_iters=MAX_ITERS))
            b.apply_updates(ops[cut:])
            np.testing.assert_array_equal(a.answers(ha), b.answers(hb))
            rows[engine] = a.answers(ha)
        np.testing.assert_array_equal(rows["dense"], rows["host"])
        np.testing.assert_array_equal(rows["dense"], rows["scratch"])

    run()


def test_cqp_serve_churn_all_engines_subprocess():
    """Acceptance: ``cqp_serve --json`` runs a churn scenario (mid-stream
    register + deregister) on all three engines via CQPSession."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + env.get(
        "PYTHONPATH", ""
    )
    for engine in ENGINES:
        out = subprocess.run(
            [
                sys.executable,
                "-m",
                "repro.launch.cqp_serve",
                "--smoke",
                "--json",
                "--engine",
                engine,
                "--register-at",
                "2",
                "--deregister-at",
                "3",
            ],
            env=env,
            capture_output=True,
            text=True,
            timeout=560,
        )
        assert out.returncode == 0, out.stdout + out.stderr
        payload = json.loads(out.stdout.strip().splitlines()[-1])
        assert payload["engine"] == engine
        assert payload["platform"] == "cpu" and payload["device_count"] >= 1
        assert payload["device_kind"]
        assert payload["registers"] == 1 and payload["deregisters"] == 1
        assert payload["updates_served"] > 0
        if engine != "scratch":
            assert payload["bytes_freed"] > 0


def test_cqp_serve_edge_capacity_is_initial_edges_plus_stream_inserts():
    """The host graph is sized to what the log can occupy: a delete frees a
    slot for a later insert, so initial + inserts is never exceeded."""
    from repro.core.graph import DynamicGraph, edge_capacity

    initial = [(0, 1, 1.0), (1, 2, 1.0), (2, 3, 1.0)]
    log = [(3, 0, 0, 1.0, +1), (0, 1, 0, 1.0, -1), (0, 2, 0, 1.0, +1),
           (3, 1, 0, 1.0, +1)]
    cap = edge_capacity(initial, log)
    assert cap == 6
    g = DynamicGraph(4, initial, capacity=cap)
    g.apply_batch(log)  # fits: no "edge capacity exhausted"
    assert g.num_edges == 5
