"""Serving-loop runtime plumbing: straggler shedding, restart exhaustion,
and slot accounting across admission rejections.

Companion to ``tests/test_serving.py`` (admission/tenancy semantics); this
file drives the StragglerDetector and the fault supervisor *through the
serving loop* rather than in isolation.
"""

from __future__ import annotations

import asyncio

import pytest

from repro.core import plan as qp
from repro.core.governor import GovernorConfig
from repro.core.graph import DynamicGraph
from repro.core.session import CQPSession
from repro.data.graphgen import powerlaw_graph, split_90_10
from repro.runtime.fault import InjectedFault
from repro.serving.admission import AdmissionRejected, SLOConfig
from repro.serving.loadgen import tenant_update_streams
from repro.serving.server import CQPServer, ServerConfig, build_serving_session
from repro.serving.tenants import TenantSpec

V, E, BATCH, MAX_ITERS = 64, 256, 8, 16
LADDER = GovernorConfig(representation="prob")


def _workload(num_batches: int = 10, seed: int = 3):
    edges = powerlaw_graph(V, E, seed=seed)
    initial, pool = split_90_10(edges, seed=seed)
    streams = tenant_update_streams(
        initial, V, 1, num_batches=num_batches, batch_size=BATCH,
        delete_fraction=0.1, insert_pool=pool, seed=seed + 1,
    )
    return initial, streams["tenant0"]


def _session(initial) -> CQPSession:
    graph = DynamicGraph(V, initial, capacity=len(initial) * 8 + 1024)
    return build_serving_session(graph, ladder=LADDER, engine="host")


# --------------------------------------------------------------- stragglers
def test_straggler_shedding_fires_exactly_once_per_event():
    """One slow chunk in an otherwise steady stream must produce exactly ONE
    straggler event, ONE force-shed, and ONE ladder action — the server
    registers its policy hook once (double registration would walk the
    ladder twice per event)."""
    initial, batches = _workload(num_batches=10)
    spike_at = 6

    def delays(k: int) -> float:
        # steady 10ms cadence with a single 100ms spike: past the warmup,
        # 100ms > threshold(4) * ewma(~10ms) flags exactly chunk `spike_at`
        return 0.1 if k == spike_at else 0.01

    # a huge backlog high-water mark and an infinite cooldown: the *only*
    # ladder action in this run can then be the straggler escalation
    cfg = ServerConfig(
        chunk_updates=BATCH,
        drop_ladder=LADDER,
        slo=SLOConfig(backlog_high_updates=10**9, cooldown_epochs=10**9),
        straggler_threshold=4.0,
        straggler_warmup=3,
    )

    async def run():
        server = CQPServer(
            _session(initial), config=cfg, delay_injector=delays
        )
        async with server:
            server.add_tenant(TenantSpec(tenant_id="t"))
            ticket = await server.register_query(
                "t", qp.sssp(0, max_iters=MAX_ITERS)
            )
            for batch in batches:
                server.submit("t", batch)
                await server.drain()  # one chunk per epoch, steady cadence
            r = await server.read(ticket, timeout_s=30.0)
            stats = server.stats()
        return r, stats

    r, stats = asyncio.run(run())
    assert r.fresh
    assert stats["straggler_events"] == 1
    assert stats["admission"]["straggler_sheds"] == 1
    straggler_actions = [
        a for a in stats["actions"]
        if a["reason"].startswith("straggler@")
    ]
    assert len(straggler_actions) == 1
    assert straggler_actions[0]["reason"] == f"straggler@{spike_at}"
    assert straggler_actions[0]["kind"] == "degrade"
    # nothing else walked the ladder
    assert len(stats["actions"]) == 1


def test_straggler_detection_disabled_without_spike():
    initial, batches = _workload(num_batches=8)

    async def run():
        server = CQPServer(
            _session(initial),
            config=ServerConfig(
                chunk_updates=BATCH,
                drop_ladder=LADDER,
                slo=SLOConfig(backlog_high_updates=10**9),
            ),
            delay_injector=lambda k: 0.005,
        )
        async with server:
            server.add_tenant(TenantSpec(tenant_id="t"))
            await server.register_query("t", qp.sssp(0, max_iters=MAX_ITERS))
            for batch in batches:
                server.submit("t", batch)
                await server.drain()
            stats = server.stats()
        return stats

    stats = asyncio.run(run())
    assert stats["straggler_events"] == 0
    assert stats["admission"]["straggler_sheds"] == 0


# ----------------------------------------------------------------- restarts
def test_restart_exhaustion_surfaces_the_fault():
    """A fault that survives every genesis rebuild must exhaust
    ``max_restarts`` and surface to callers — not spin forever.  The fault
    count is restarts + 1 (the final attempt re-raises)."""
    initial, batches = _workload(num_batches=2)
    max_restarts = 2

    def factory() -> CQPSession:
        return _session(initial)

    def always_fail(k: int) -> None:
        raise InjectedFault("unrecoverable scripted fault")

    async def run():
        server = CQPServer(
            factory(),
            config=ServerConfig(
                chunk_updates=BATCH,
                drop_ladder=LADDER,
                max_restarts=max_restarts,
            ),
            session_factory=factory,
            fault_injector=always_fail,
        )
        await server.start()
        server.add_tenant(TenantSpec(tenant_id="t"))
        await server.register_query("t", qp.sssp(0, max_iters=MAX_ITERS))
        server.submit("t", batches[0])
        with pytest.raises(InjectedFault):
            await server.drain()
        # the loop is dead: every later call re-raises rather than hanging
        with pytest.raises(InjectedFault):
            server.submit("t", batches[1])
        faults = server.faults
        with pytest.raises(InjectedFault):
            await server.stop()
        return faults

    assert asyncio.run(run()) == max_restarts + 1


# -------------------------------------------------------------------- slots
def test_admission_rejects_do_not_leak_query_slots():
    """register → shed-reject → re-register round-trips must leave the
    session's slot pool exactly as a straight registration would: a
    rejected registration never reached the engine, so it must not consume
    a slot, a qid, or a ticket binding."""
    initial, batches = _workload(num_batches=4)

    async def run():
        server = CQPServer(
            _session(initial),
            config=ServerConfig(chunk_updates=BATCH, drop_ladder=LADDER),
        )
        async with server:
            server.add_tenant(TenantSpec(tenant_id="t"))
            first = await server.register_query(
                "t", qp.sssp(0, max_iters=MAX_ITERS)
            )
            assert server.session.stats()["active_queries"] == 1

            for _ in range(3):  # repeated rejects: still no slot motion
                server.admission.shedding = True
                with pytest.raises(AdmissionRejected):
                    await server.register_query(
                        "t", qp.sssp(1, max_iters=MAX_ITERS)
                    )
                server.admission.shedding = False
            stats_mid = server.stats()
            assert server.session.stats()["active_queries"] == 1
            assert stats_mid["tenants"]["t"]["queries"] == 1
            assert stats_mid["tenants"]["t"]["rejected_registers"] == 3

            second = await server.register_query(
                "t", qp.sssp(1, max_iters=MAX_ITERS)
            )
            assert server.session.stats()["active_queries"] == 2
            # both tickets stay live through maintenance
            for batch in batches:
                server.submit("t", batch)
            await server.drain()
            r1 = await server.read(first, timeout_s=30.0)
            r2 = await server.read(second, timeout_s=30.0)
            assert r1.fresh and r2.fresh

            freed = await server.deregister_query(second)
            assert freed >= 0
            assert server.session.stats()["active_queries"] == 1
            await server.deregister_query(first)
            assert server.session.stats()["active_queries"] == 0
            stats = server.stats()
        return stats

    stats = asyncio.run(run())
    assert stats["tenants"]["t"]["queries"] == 0


# ----------------------------------------------------------------- obs sinks
def test_obs_scrape_logs_an_unwritable_sink_and_raises_anything_else(
    tmp_path, caplog, monkeypatch
):
    """A file sink that cannot be written is logged and serving goes on; an
    error in publishing the session's metrics is a program fault and
    propagates instead of vanishing."""
    initial, _ = _workload(num_batches=1)
    server = CQPServer(
        _session(initial),
        config=ServerConfig(
            chunk_updates=BATCH,
            metrics_out=str(tmp_path / "missing-dir" / "metrics.json"),
        ),
    )
    with caplog.at_level("ERROR", logger="repro.serving"):
        server._obs_scrape()
    assert "obs sink write failed" in caplog.text

    def broken(*_a, **_kw):
        raise RuntimeError("probe bug")

    monkeypatch.setattr(server.session, "publish_metrics", broken)
    with pytest.raises(RuntimeError, match="probe bug"):
        server._obs_scrape()
