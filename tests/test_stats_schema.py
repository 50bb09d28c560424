"""Golden key-set schema regression: ``session.stats()`` / ``server.stats()``.

The stats dicts are the JSON contract every consumer scrapes — the serving
tier, the obs registry bridge (``publish_session_metrics``), CI smokes, and
downstream dashboards.  These tests pin the key sets: a PR that renames,
drops, or adds a key fails here first and must update the goldens
deliberately (DESIGN.md §15).
"""

from __future__ import annotations

import asyncio

import numpy as np
import pytest

from repro.core import plan as qp
from repro.core.engine import MaintainStats
from repro.core.governor import GovernorConfig
from repro.core.graph import DynamicGraph
from repro.core.session import ENGINES, CQPSession
from repro.data.graphgen import powerlaw_graph, split_90_10
from repro.serving.loadgen import tenant_update_streams
from repro.serving.server import CQPServer, ServerConfig, build_serving_session
from repro.serving.tenants import TenantSpec

V, E, BATCH, MAX_ITERS = 64, 256, 8, 16

# ------------------------------------------------------------------- goldens
SESSION_KEYS = frozenset({
    "engine",
    "active_queries",
    "registered_total",
    "deregistered_total",
    "updates_applied",
    "bytes_freed_total",
    "bytes_shed_total",
    "nbytes",
    "nbytes_per_query",
    "nbytes_per_operator",
    "query_qids",
    "last_maintain",
})
SESSION_DENSE_EXTRA = frozenset({"slot_capacity", "shards"})
LAST_MAINTAIN_KEYS = frozenset({
    "iters_run",
    "scheduled",
    "changed",
    "repairs",
    "written",
    "removed",
    "dropped",
    "jwritten",
    "det_overflow",
    "active_edges",
    "sched_sizes",
    "frontier_sizes",
})

SERVER_KEYS = frozenset({
    "epochs",
    "covered_updates",
    "admitted_total",
    "queue_depth",
    "chunks_applied",
    "faults",
    "tenants",
    "admission",
    "actions",
    "phases",
    "straggler_events",
    "session",
})
TENANT_KEYS = frozenset({
    "priority",
    "level",
    "queries",
    "nbytes",
    "budget_bytes",
    "rate_per_s",
    "watermark",
    "submitted_updates",
    "admitted_updates",
    "rejected_updates",
    "rejected_registers",
    "read_latency",
    "freshness_lag_updates",
    "stale_reads",
})
PHASE_KEYS = frozenset(
    {"count", "p50_ms", "p99_ms", "p999_ms", "mean_ms", "max_ms", "total_s"}
)


def _workload():
    edges = powerlaw_graph(V, E, seed=0)
    initial, pool = split_90_10(edges, seed=0)
    return edges, initial, pool


# ------------------------------------------------------------------- session
@pytest.mark.parametrize("engine", ENGINES)
def test_session_stats_golden_keys(engine):
    _, initial, pool = _workload()
    s = CQPSession(
        DynamicGraph(V, initial, capacity=len(initial) * 4 + 64), engine=engine
    )
    s.register(qp.sssp(0, max_iters=MAX_ITERS))
    s.apply_updates([(u, w, 0, x, +1) for (u, w, x) in pool[:6]])
    got = set(s.stats())
    want = SESSION_KEYS | (SESSION_DENSE_EXTRA if engine == "dense" else set())
    assert got == want, (
        f"session.stats() schema drifted for {engine}: "
        f"+{sorted(got - want)} -{sorted(want - got)}"
    )
    assert set(s.stats()["last_maintain"]) == LAST_MAINTAIN_KEYS
    assert set(MaintainStats._fields) == LAST_MAINTAIN_KEYS


def test_session_stats_governor_and_runtime_blocks_are_opt_in():
    _, initial, pool = _workload()
    s = CQPSession(
        DynamicGraph(V, initial, capacity=len(initial) * 4 + 64),
        engine="dense",
        budget_bytes=1 << 20,
        governor=GovernorConfig(representation="prob"),
    )
    s.register(qp.sssp(0, max_iters=MAX_ITERS))
    s.apply_updates([(u, w, 0, x, +1) for (u, w, x) in pool[:4]])
    got = set(s.stats())
    want = SESSION_KEYS | SESSION_DENSE_EXTRA | {"governor"}
    assert got == want, f"+{sorted(got - want)} -{sorted(want - got)}"


# -------------------------------------------------------------------- server
def test_server_stats_golden_keys():
    _, initial, pool = _workload()
    streams = tenant_update_streams(
        initial, V, 2, num_batches=3, batch_size=BATCH,
        delete_fraction=0.1, insert_pool=pool, seed=1,
    )
    ladder = GovernorConfig(representation="prob")

    async def run():
        session = build_serving_session(
            DynamicGraph(V, initial, capacity=len(initial) * 8 + 1024),
            ladder=ladder,
            engine="host",
        )
        server = CQPServer(
            session,
            config=ServerConfig(chunk_updates=BATCH, drop_ladder=ladder),
        )
        async with server:
            for i, tid in enumerate(sorted(streams)):
                server.add_tenant(TenantSpec(tenant_id=tid, priority=i + 1))
                await server.register_query(tid, qp.sssp(i, max_iters=MAX_ITERS))
                for batch in streams[tid]:
                    server.submit(tid, batch)
            await server.drain()
            return server.stats()

    st = asyncio.run(run())
    got = set(st)
    assert got == SERVER_KEYS, (
        f"server.stats() schema drifted: "
        f"+{sorted(got - SERVER_KEYS)} -{sorted(SERVER_KEYS - got)}"
    )
    # the in-server session block carries the runtime observers on top of
    # the session golden (host engine: no dense-only extras)
    assert set(st["session"]) == SESSION_KEYS | {"runtime"}
    for tid, tstats in st["tenants"].items():
        assert set(tstats) == TENANT_KEYS, tid
    for phase, block in st["phases"].items():
        assert set(block) == PHASE_KEYS, phase
    assert st["covered_updates"] == sum(
        len(b) for s_ in streams.values() for b in s_
    )
    assert np.isfinite(st["admission"]["p99_ms"])
