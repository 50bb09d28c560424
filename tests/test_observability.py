"""Unified observability layer: tracer, metrics registry, DC probes.

DESIGN.md §15.  Covers:

* the span tracer — zero-allocation disabled path, bounded ring buffer,
  Chrome-trace export that passes the structural validator;
* the typed metrics registry — counters/gauges/histograms, label series,
  JSON snapshot, Prometheus text exposition;
* span coverage end to end — update-batch/ingest/dispatch/sync/snapshot
  spans from the engines and the session, their mirror on the
  ``jax.profiler`` trace, governor escalation spans, checkpoint spans;
* cross-engine ``MaintainStats`` parity — dense/host/scratch emit the same
  stat keys, zero-filled where a counter is structurally absent;
* Bloom probe math — the analytic FP estimate vs brute-force membership
  probing, and the FP-rate gauge rising monotonically as dropped diffs
  are inserted.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from repro.core import bloom as bloom_lib
from repro.core import dropping as dr
from repro.core import plan as qplan
from repro.core.engine import ITER_TRACE, MaintainStats
from repro.core.graph import DynamicGraph
from repro.core.session import ENGINES, CQPSession
from repro.obs import metrics as obs_metrics
from repro.obs import probes
from repro.obs import trace as obs_trace

V = 16
MAX_ITERS = 16


def _workload(seed: int = 5):
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


def _session(initial, engine, **kw) -> CQPSession:
    return CQPSession(DynamicGraph(V, initial, capacity=256), engine=engine, **kw)


@pytest.fixture
def tracer():
    """A live tracer installed as the process default; restored after."""
    t = obs_trace.Tracer()
    prev = obs_trace.get_tracer()
    obs_trace.set_tracer(t)
    try:
        yield t
    finally:
        obs_trace.set_tracer(prev)


# ------------------------------------------------------------------- tracer
def test_disabled_tracer_is_zero_allocation_noop():
    """The default (disabled) tracer hands back ONE shared null span —
    tracing-off serving paths never allocate per call."""
    obs_trace.set_tracer(None)
    s1 = obs_trace.span("a", "sweep", pid="x", n=1)
    s2 = obs_trace.span("b", "sweep", pid="y", n=2)
    assert s1 is s2 is obs_trace.NULL_SPAN
    with s1 as sp:
        sp.set(anything=1)  # no-op, no error
    obs_trace.instant("evt", "sweep")
    off = obs_trace.Tracer(enabled=False, profiler=True)
    assert off.span("c", "sweep") is obs_trace.NULL_SPAN
    assert not obs_trace.NULL_SPAN.live
    assert obs_trace.get_tracer().events() == off.events() == []


def test_span_records_duration_nesting_and_args(tracer):
    with obs_trace.span("outer", "update_batch", pid="engine:test", tid=3, a=1) as sp:
        with obs_trace.span("inner", "dispatch", pid="engine:test"):
            pass
        sp.set(b=2)
    evs = tracer.events()
    assert [e["name"] for e in evs] == ["inner", "outer"]  # close order
    outer = evs[1]
    assert outer["ph"] == "X" and outer["cat"] == "update_batch"
    assert outer["pid"] == "engine:test" and outer["tid"] == 3
    assert outer["args"] == {"a": 1, "b": 2}
    assert outer["dur"] >= evs[0]["dur"] >= 0
    assert outer["ts"] <= evs[0]["ts"]


def test_ring_buffer_bounds_and_drop_accounting():
    t = obs_trace.Tracer(capacity=4)
    for i in range(10):
        with t.span(f"s{i}", "sweep"):
            pass
    assert len(t.events()) == 4
    assert t.emitted_events == 10
    assert t.dropped_events == 6
    assert [e["name"] for e in t.events()] == ["s6", "s7", "s8", "s9"]


def test_chrome_trace_export_validates(tracer, tmp_path):
    with obs_trace.span("sweep", "sweep", pid="engine:dense", tid=0, n=3):
        pass
    tracer.instant("shed", "admission", pid="serving", tid="t0")
    with tracer.span("ingest", "ingest", pid="engine:dense", chunk_lo=0):
        pass
    out = tmp_path / "trace.json"
    n = tracer.export(str(out))
    payload = json.loads(out.read_text())
    assert n == 3 and len(payload["traceEvents"]) == 3
    assert obs_trace.validate_chrome_trace(payload) == []


def test_validator_flags_malformed_traces():
    assert obs_trace.validate_chrome_trace([]) != []  # not object form
    bad = {"traceEvents": [{"ph": "X", "name": "x", "ts": 0}]}  # no dur
    assert obs_trace.validate_chrome_trace(bad) != []
    ok = {"traceEvents": [{"ph": "i", "name": "x", "ts": 0.0, "pid": "p", "tid": 0}]}
    assert obs_trace.validate_chrome_trace(ok) == []


# ----------------------------------------------------------------- registry
def test_counter_gauge_histogram_and_labels():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("req_total", "requests")
    c.inc()
    c.inc(2, tenant="a")
    assert c.value() == 1 and c.value(tenant="a") == 2
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("depth", "queue depth")
    g.set(5)
    g.inc(-2)
    assert g.value() == 3
    h = reg.histogram("lat", "latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    snap = reg.snapshot()
    series = snap["lat"]["series"][0]
    assert series["count"] == 3
    assert series["buckets"] == {"0.1": 1, "1.0": 2}  # cumulative; +Inf=count
    json.dumps(snap)  # JSON-safe end to end


def test_registry_registration_is_idempotent_and_typed():
    reg = obs_metrics.MetricsRegistry()
    a = reg.counter("x_total", "x")
    assert reg.counter("x_total", "x") is a
    with pytest.raises(ValueError):
        reg.gauge("x_total", "x")  # same name, different type
    assert reg.get("x_total") is a
    assert reg.get("missing") is None


def test_prometheus_text_exposition():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("updates_applied_total", "ingested").inc(3, engine="dense")
    reg.counter("repairs", "repairs").inc(2)
    reg.gauge("nbytes", "bytes").set(10)
    reg.histogram("sweep_s", "sweep time", buckets=(0.5,)).observe(0.1)
    text = reg.prometheus_text()
    # counters end in _total exactly once
    assert 'updates_applied_total{engine="dense"} 3' in text
    assert "repairs_total 2" in text and "repairs_total_total" not in text
    assert "# TYPE nbytes gauge" in text and "nbytes 10" in text
    assert 'sweep_s_bucket{le="0.5"} 1' in text
    assert 'sweep_s_bucket{le="+Inf"} 1' in text
    assert "sweep_s_count 1" in text


# ------------------------------------------------------- span coverage e2e
def test_host_engine_emits_update_batch_and_sweep_spans(tracer):
    initial, log = _workload()
    s = _session(initial, "host")
    s.register(qplan.sssp(0, max_iters=MAX_ITERS))
    s.apply_updates(log)
    cats = {e["cat"] for e in tracer.events()}
    assert {"update_batch", "sweep"} <= cats
    sweep = [e for e in tracer.events() if e["cat"] == "sweep"][-1]
    assert sweep["pid"] == "engine:host"
    assert sweep["args"]["iters_run"] >= 1


def test_dense_batched_emits_sweep_and_kernel_dispatch_spans(tracer):
    initial, log = _workload()
    s = _session(initial, "dense", batch_capacity=4)
    s.register(qplan.sssp(0, max_iters=MAX_ITERS))
    s.apply_updates_batched(log, batch_size=4)
    s.answers_snapshot()
    by_cat: dict[str, list] = {}
    for e in tracer.events():
        by_cat.setdefault(e["cat"], []).append(e)
    assert {"update_batch", "ingest", "dispatch", "sync", "snapshot"} <= set(by_cat)
    # one ingest and one dispatch span a chunk, one sync a log
    chunks = -(-len(log) // 4)
    assert len(by_cat["ingest"]) == len(by_cat["dispatch"]) == chunks
    assert len(by_cat["sync"]) == 1
    # session- and engine-level ingestion spans nest under the same cat
    pids = {e["pid"] for e in by_cat["update_batch"]}
    assert {"session", "engine:dense"} <= pids
    outer = [e for e in by_cat["update_batch"] if e["pid"] == "engine:dense"][-1]
    assert outer["args"]["iters_run"] >= 1
    # the per-iteration probe series rides on the update_batch span
    assert len(outer["args"]["sched_sizes"]) >= 1
    assert by_cat["dispatch"][0]["args"]["backend"] == "coo"
    # ingest, dispatch and sync nest inside the engine's update_batch span
    t0, t1 = outer["ts"], outer["ts"] + outer["dur"]
    for cat in ("ingest", "dispatch", "sync"):
        for e in by_cat[cat]:
            assert t0 <= e["ts"] and e["ts"] + e["dur"] <= t1 + 1e-3, cat


def test_governor_escalation_emits_governor_spans(tracer):
    initial, log = _workload(seed=7)
    s = _session(initial, "dense", budget_bytes=1)  # force escalation
    s.register(qplan.sssp(0, max_iters=MAX_ITERS))
    s.apply_updates(log[:4])
    gov = [e for e in tracer.events() if e["cat"] == "governor"]
    assert gov, "no governor spans despite a 1-byte budget"
    assert gov[0]["name"] in ("escalate", "deescalate")
    assert {"qid", "op", "level_from", "level_to"} <= set(gov[0]["args"])


def test_checkpoint_emits_span_and_registry_counters(tracer, tmp_path):
    from repro.runtime.recovery import RecoverySupervisor

    initial, log = _workload()
    s = _session(initial, "host")
    s.register(qplan.sssp(0, max_iters=MAX_ITERS))
    s.apply_updates(log[:4])
    reg = obs_metrics.get_registry()
    before = reg.counter("cqp_checkpoints_total", "checkpoints written").value()
    sup = RecoverySupervisor(
        str(tmp_path), restore_fn=lambda d: (s, 0), async_write=False
    )
    sup.checkpoint(s, next_chunk=1)
    ck = [e for e in tracer.events() if e["cat"] == "checkpoint"]
    assert ck and ck[-1]["pid"] == "recovery"
    assert ck[-1]["args"]["nbytes"] > 0
    assert reg.counter("cqp_checkpoints_total", "").value() == before + 1
    assert reg.gauge("cqp_checkpoint_last_bytes", "").value() > 0


# -------------------------------------------- cross-engine stats parity (S2)
@pytest.mark.parametrize("engine", ENGINES)
def test_last_stats_is_maintain_stats_everywhere(engine):
    initial, log = _workload()
    s = _session(initial, engine)
    s.register(qplan.sssp(0, max_iters=MAX_ITERS))
    s.apply_updates(log)
    ls = s.last_stats
    assert ls is not None and tuple(ls._fields) == MaintainStats._fields
    lm = s.stats()["last_maintain"]
    assert set(lm) == set(MaintainStats._fields)
    assert lm["iters_run"] >= 1 and lm["scheduled"] >= 1
    # per-iteration probe vectors: trimmed to iterations run, bounded
    n = min(lm["iters_run"], ITER_TRACE)
    assert len(lm["sched_sizes"]) == n == len(lm["frontier_sizes"])


def test_cross_engine_key_parity_and_zero_fill():
    initial, log = _workload()
    views = {}
    for engine in ENGINES:
        s = _session(initial, engine)
        s.register(qplan.sssp(0, max_iters=MAX_ITERS))
        s.apply_updates(log)
        views[engine] = s.stats()["last_maintain"]
    key_sets = {e: set(v) for e, v in views.items()}
    assert key_sets["dense"] == key_sets["host"] == key_sets["scratch"]
    # structurally-absent counters are REPORTED, zero-filled: the host
    # pointer machine has no Det/Bloom drop store or join store...
    for k in ("dropped", "jwritten", "det_overflow"):
        assert views["host"][k] == 0
    # ...and from-scratch re-execution never repairs or drops
    for k in ("repairs", "dropped", "det_overflow"):
        assert views["scratch"][k] == 0
    # scratch's analytic schedule series accounts every (q, v) relaxation
    assert sum(views["scratch"]["sched_sizes"]) == views["scratch"]["scheduled"]


# ----------------------------------------------------------- Bloom math (S3)
def test_bloom_fp_rate_analytic_matches_brute_force():
    """fill^k vs empirically probing never-inserted keys on a small filter."""
    k = 4
    flt = bloom_lib.make((), num_bits=512, num_hashes=k)
    rng = np.random.default_rng(0)
    n = 64
    v_ins = rng.integers(0, 1 << 20, size=n).astype(np.uint32)
    i_ins = rng.integers(0, 32, size=n).astype(np.uint32)
    flt = bloom_lib.insert(flt, v_ins, i_ins, np.ones(n, bool))
    fill = float(bloom_lib.fill_fraction(flt))
    analytic = probes.bloom_fp_rate(fill, k)
    assert 0.05 < fill < 0.9 and 0.0 < analytic < 0.5
    # no false negatives, ever
    assert bool(np.asarray(bloom_lib.query(flt, v_ins, i_ins)).all())
    # brute-force FP rate over disjoint keys (vertex ids past the insert range)
    m = 4000
    v_neg = rng.integers(1 << 20, 1 << 24, size=m).astype(np.uint32)
    i_neg = rng.integers(0, 32, size=m).astype(np.uint32)
    hits = np.asarray(bloom_lib.query(flt, v_neg, i_neg))
    empirical = float(hits.mean())
    assert abs(empirical - analytic) < 0.02, (empirical, analytic)


def test_bloom_fp_rate_gauge_rises_with_dropped_diffs():
    """Prob-Drop session: every maintained batch inserts dropped diffs, so
    the published FP-rate gauge is non-decreasing and ends positive."""
    initial, log = _workload(seed=3)
    s = _session(
        initial,
        "dense",
        drop=dr.DropConfig(mode="prob", bloom_bits=256, bloom_hashes=4),
    )
    h = s.register(qplan.sssp(0, max_iters=MAX_ITERS))
    # the session-level config provisions the representation; the per-query
    # POLICY row is what actually selects drops
    s.set_drop_policy(h, dr.DropConfig(mode="prob", p=1.0, bloom_bits=256))
    reg = obs_metrics.MetricsRegistry()
    rates = []
    for k in range(0, len(log), 3):
        s.apply_updates(log[k : k + 3])
        probes.publish_session_metrics(s, reg)
        rates.append(reg.gauge("cqp_bloom_fp_rate", "").value(qid=h.qid))
    assert all(b >= a for a, b in zip(rates, rates[1:])), rates
    assert rates[-1] > 0.0
    fill = reg.gauge("cqp_bloom_fill_ratio", "").value(qid=h.qid)
    assert rates[-1] == pytest.approx(probes.bloom_fp_rate(fill, 4))


# ------------------------------------------------------------ session scrape
def test_publish_session_metrics_scrape_is_idempotent():
    initial, log = _workload()
    s = _session(initial, "host")
    s.register(qplan.sssp(0, max_iters=MAX_ITERS))
    s.apply_updates(log[:6])
    reg = obs_metrics.MetricsRegistry()
    probes.publish_session_metrics(s, reg)
    v1 = reg.counter("cqp_updates_applied_total", "").value()
    probes.publish_session_metrics(s, reg)  # double scrape: no double count
    assert reg.counter("cqp_updates_applied_total", "").value() == v1 == 6
    s.apply_updates(log[6:8])
    probes.publish_session_metrics(s, reg)
    assert reg.counter("cqp_updates_applied_total", "").value() == 8
    assert reg.gauge("cqp_active_queries", "").value() == 1
    assert reg.gauge("cqp_nbytes", "").value() == s.nbytes()
    # per-operator occupancy gauge carries (qid, op) labels
    occ = reg.get("cqp_diffstore_bytes")
    assert occ is not None and len(occ.series()) >= 1
