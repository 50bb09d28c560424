"""The program's own names on the device trace (DESIGN.md §15).

* ``Tracer(profiler=True)`` mirrors every live span into the
  ``jax.profiler`` trace as ``cqp.<name>``, nested in the caller's
  annotations; a disabled tracer opens no annotation at all;
* the chunk program is named ``jit_cqp_chunk_step`` and carries the
  ``cqp.*`` phase scopes in its op metadata, with and without Prob-Drop and
  on the fused backend;
* ``MaintainStats.active_edges`` is the sum, over the sweep's iterations,
  of the live in-edges of the rows each iteration recomputes
  (``sched | repair``), and the COO and ELL backends count alike.
"""

from __future__ import annotations

import glob
import os
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.core import dropping as dr
from repro.core import engine as eng
from repro.core import plan as qplan
from repro.core import queries as q
from repro.core.graph import DynamicGraph
from repro.core.session import CQPSession
from repro.obs import trace as obs_trace

V = 24
MAX_ITERS = 24
PROB = dr.DropConfig(mode="prob", p=0.5, bloom_bits=1 << 10, bloom_hashes=2)


def _workload(seed: int = 3, e: int = 96):
    """(initial edges, three update batches of inserts and deletes)."""
    rng = np.random.default_rng(seed)
    seen = {}
    while len(seen) < e:
        u, w = int(rng.integers(0, V)), int(rng.integers(0, V))
        if u != w:
            seen[(u, w)] = (u, w, float(rng.integers(1, 10)))
    edges = list(seen.values())
    initial, pool = edges[: e * 3 // 4], edges[e * 3 // 4 :]
    present = [(u, w) for (u, w, _x) in initial]
    batches = []
    for _ in range(3):
        batch = []
        for _ in range(5):
            if rng.random() < 0.4:
                u, w = present.pop(int(rng.integers(0, len(present))))
                batch.append((u, w, 0, 1.0, -1))
            else:
                u, w, x = pool.pop()
                batch.append((u, w, 0, x, +1))
                present.append((u, w))
        batches.append(batch)
    return initial, batches


@pytest.fixture
def installed():
    """Install a tracer for one test; the disabled default comes back."""
    prev = obs_trace.get_tracer()
    yield obs_trace.set_tracer
    obs_trace.set_tracer(prev)


# ------------------------------------------------------- the profiler mirror
def _host_events(directory: str) -> list[tuple[str, float, float]]:
    (path,) = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    return [
        (e.name, e.start_ns, e.start_ns + e.duration_ns)
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines
        for e in line.events
        if e.name.startswith(("cqp.", "caller"))
    ]


def test_profiler_tracer_spans_land_in_the_jax_trace(installed, tmp_path):
    initial, batches = _workload()
    tracer = installed(obs_trace.Tracer(profiler=True))
    s = CQPSession(DynamicGraph(V, initial, capacity=256), engine="dense",
                   batch_capacity=4)
    s.register(qplan.sssp(0, max_iters=MAX_ITERS))
    s.apply_updates_batched(batches[0], batch_size=4)  # compile outside
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with TraceAnnotation("caller"):
        s.apply_updates_batched(batches[1], batch_size=4)
        s.answers_snapshot()
    jax.profiler.stop_trace()

    events = _host_events(str(tmp_path))
    ((_, c0, c1),) = [e for e in events if e[0] == "caller"]
    names = {n for (n, s0, s1) in events if c0 <= s0 and s1 <= c1}
    assert {"cqp.ingest", "cqp.dispatch", "cqp.sync", "cqp.snapshot",
            "cqp.update_batch"} <= names
    # the spans also stay in the ring buffer, with their durations
    ring = {e["name"] for e in tracer.events()}
    assert {"ingest", "dispatch", "sync", "snapshot"} <= ring


def test_disabled_tracer_opens_no_annotation(installed, monkeypatch):
    opened = []

    class Counting(TraceAnnotation):
        def __init__(self, name, **kw):
            opened.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    initial, batches = _workload()
    installed(obs_trace.Tracer(enabled=False, profiler=True))
    s = CQPSession(DynamicGraph(V, initial, capacity=256), engine="dense",
                   batch_capacity=4)
    s.register(qplan.sssp(0, max_iters=MAX_ITERS))
    s.apply_updates_batched(batches[0], batch_size=4)
    s.answers_snapshot()
    assert opened == []
    # the same run with the tracer on opens one per span
    installed(obs_trace.Tracer(profiler=True))
    s.apply_updates_batched(batches[1], batch_size=4)
    assert "cqp.dispatch" in opened and "cqp.ingest" in opened


# ------------------------------------------------------------ named programs
SCOPES = {
    ("coo", False): {"edge_scatter", "edge_order", "expand", "frontier", "store"},
    ("coo", True): {"edge_scatter", "edge_order", "expand", "frontier", "store",
                    "drop"},
    ("fused", True): {"edge_scatter", "edge_order", "fused_sweep", "frontier",
                      "drop"},
}


@pytest.mark.parametrize("backend,prob", sorted(SCOPES))
def test_chunk_step_hlo_carries_the_phase_scopes(backend, prob):
    initial, batches = _workload()
    e = q.sssp(DynamicGraph(V, initial, capacity=512), [0, V // 2],
               max_iters=MAX_ITERS, backend=backend, batch_capacity=8,
               drop=PROB if prob else None)
    upd = e._ingest_chunk(e.graph.apply_batch_resolved(batches[0]), 8)
    text = e._step.lower(e.state, e.g, upd).as_text(debug_info=True)
    assert "module @jit_cqp_chunk_step" in text
    assert SCOPES[backend, prob] <= set(re.findall(r"cqp\.([a-z_]+)", text))
    # the sweep program alone, as register and the host path dispatch it
    dirty = np.ones(V, bool)
    text = e._maintain.lower(e.state, e.g, dirty).as_text(debug_info=True)
    assert "module @jit_cqp_maintain" in text


# -------------------------------------------------------------- active edges
def _recount(e: eng.DiffIFE, batch, monkeypatch) -> tuple[float, float]:
    """(the program's active_edges, a NumPy recount) for one chunk: the
    sweep's while_loop runs as a Python loop that keeps every carry."""
    carries = []

    def keep(cond, body, c):
        carries.append(c)
        while bool(cond(c)):
            c = body(c)
            carries.append(c)
        return c

    upd = e._ingest_chunk(e.graph.apply_batch_resolved(batch), 8)
    with monkeypatch.context() as m:
        m.setattr(jax.lax, "while_loop", keep)
        e.state, e.g, stats = eng.batched_step(e.cfg, e.state, e.g, upd)
    carries = [c for c in carries if isinstance(c, eng._Carry)]
    in_degree = np.asarray(e.g.in_degree)
    dirty = np.zeros(V + 1, bool)
    dirty[np.asarray(upd.dirty_v)] = True
    active = np.asarray(e.state.active)[:, None]
    want = 0
    for before, after in zip(carries, carries[1:]):
        sched = (np.asarray(before.frontier) | dirty[None, :V]) & active
        repair = np.asarray(after.repair_counts) > np.asarray(before.repair_counts)
        want += int((in_degree[None, :] * (sched | repair)).sum())
    return float(stats.active_edges), float(want)


@pytest.mark.parametrize("drop", [None, PROB], ids=["nodrop", "prob"])
def test_active_edges_matches_numpy_recount(drop, monkeypatch):
    initial, batches = _workload()
    e = q.sssp(DynamicGraph(V, initial, capacity=512), [0, V // 2, 3],
               max_iters=MAX_ITERS, batch_capacity=8, drop=drop)
    repairs = 0
    for batch in batches:
        got, want = _recount(e, batch, monkeypatch)
        assert got == want > 0
        repairs += int(e.state.repair_counts.sum())
    if drop is not None:
        assert repairs > 0  # the repair rows were counted, not just sched


def test_active_edges_agree_across_backends():
    initial, batches = _workload()
    counts = {}
    for backend in ("coo", "ell"):
        e = q.sssp(DynamicGraph(V, initial, capacity=512), [0, V // 2, 3],
                   max_iters=MAX_ITERS, backend=backend, batch_capacity=8,
                   drop=PROB)
        counts[backend] = [float(e.apply_updates_batched(b).active_edges)
                           for b in batches]
    assert counts["coo"] == counts["ell"]
    assert min(counts["coo"]) > 0
