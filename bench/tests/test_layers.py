"""bench/layers.py on synthetic traces: the program's spans split the
harness's idle time without moving its totals, and device time sums by the
``cqp.*`` scope of each leaf op of the chunk program."""

from __future__ import annotations

import time

import pytest

from bench import harness, layers, trace_reduce
from bench.tests.conftest import tiny

DEV, HOST = "/device:TPU:0", "/host:CPU"
MS = 1e6  # nanoseconds
# the compiled chunk program's scope of each op, as hlo_scopes reads it
HLO = {"fusion.1": "expand", "fusion.2": "store"}


def _ev(plane, line, name, start_ms, dur_ms):
    return (plane, line, name, start_ms * MS, dur_ms * MS)


def _synthetic():
    return [
        _ev(HOST, "python", "bench.window", 10, 100),
        _ev(HOST, "python", "bench.apply", 10, 60),
        _ev(HOST, "python", "cqp.update_batch", 10, 60),
        _ev(HOST, "python", "cqp.ingest", 10, 8),
        _ev(HOST, "python", "cqp.dispatch", 18, 2),
        _ev(HOST, "python", "cqp.sync", 20, 50),
        _ev(HOST, "python", "bench.snapshot", 70, 20),
        _ev(HOST, "python", "cqp.snapshot", 72, 16),
        _ev(HOST, "python", "bench.wait", 90, 20),
        # device: one run of the chunk program holding a while loop, and a
        # copy of the snapshot's own program
        _ev(DEV, "XLA Modules", "jit_cqp_chunk_step(12)", 20, 40),
        _ev(DEV, "XLA Ops", "%while.16 = (...) while(...)", 20, 40),
        _ev(DEV, "XLA Ops", "%fusion.1 = f32[16] fusion(...)", 20, 30),
        _ev(DEV, "XLA Ops", "%fusion.2 = f32[16] fusion(...)", 50, 6),
        _ev(DEV, "XLA Ops", "%copy.3 = f32[16] copy(...)", 56, 4),
        _ev(DEV, "XLA Modules", "jit_copy(13)", 75, 5),
        _ev(DEV, "XLA Ops", "%copy.4 = f32[16] copy(...)", 75, 5),
    ]


def test_program_spans_split_the_harness_idle_and_keep_its_totals():
    events = _synthetic()
    before = trace_reduce.reduce([e for e in events if not e[2].startswith("cqp.")])
    after = trace_reduce.reduce(events)
    # the harness's own reduction reads the same with the program's spans in
    for key in ("window_s", "busy_s", "idle_gaps", "device_ops", "modules"):
        assert after[key] == before[key], key
    gaps = dict(layers.idle_gaps(events))
    # [10, 18] ingest, [18, 20] dispatch, [60, 70] the sync's wait;
    # [70, 72] and [88, 90] the harness's snapshot span, [72, 75] and
    # [80, 88] the program's; [90, 110] the generator's wait
    assert gaps == pytest.approx({"cqp.ingest": 0.008, "cqp.dispatch": 0.002,
                                  "cqp.sync": 0.01, "bench.snapshot": 0.004,
                                  "cqp.snapshot": 0.011, "bench.wait": 0.02})
    assert sum(gaps.values()) == pytest.approx(sum(dict(before["idle_gaps"]).values()))
    assert sum(gaps.values()) == pytest.approx(before["window_s"] - before["busy_s"])


def test_scopes_sum_leaf_ops_of_the_chunk_program():
    sc = layers.scopes(_synthetic(), HLO)
    assert sc["count"] == 1 and sc["module_s"] == pytest.approx(0.04)
    # the while container is not counted again; the snapshot's copy ran
    # outside the chunk program
    assert sc["scopes"] == pytest.approx({"expand": 0.03, "store": 0.006,
                                          "unscoped": 0.004})
    assert sum(sc["scopes"].values()) == pytest.approx(sc["module_s"])


HLO_TEXT = """\
%fused_scatter (p0: pred[64], p1: s32[8]) -> pred[64] {
  %p0 = pred[64]{0} parameter(0)
  %p1 = s32[8]{0} parameter(1)
  ROOT %scatter.9 = pred[64]{0} scatter(%p0, %p1), to_apply=%or
}

%fused_select (p0: f32[16]) -> f32[16] {
  %p0.1 = f32[16]{0} parameter(0)
  ROOT %select.1 = f32[16]{0:T(128)} select(%p0.1), metadata={op_name="jit(cqp_chunk_step)/while/body/cqp.store/select_n"}
}

ENTRY %main (a: f32[16], b: s32[8], c: pred[64]) -> f32[16] {
  %a = f32[16]{0} parameter(0)
  %fusion.2 = f32[16]{0:T(128)S(1)} fusion(%a), kind=kLoop, calls=%fused_select
  %hash.3 = s32[8]{0} multiply(%b, %b), metadata={op_name="jit(cqp_chunk_step)/cqp.store/cqp.drop/mul"}
  %sort.4 = (s32[8]{0:T(1024)}, pred[8]{0}) sort(%hash.3, %c), dimensions={0}, to_apply=%compare
  %gte.5 = s32[8]{0} get-tuple-element(%sort.4), index=0
  %fusion.6 = pred[64]{0:T(1024)(128)(4,1)S(1)} fusion(%c, %gte.5), kind=kCustom, calls=%fused_scatter
  %and.7 = pred[16]{0} and(%c, %c), metadata={op_name="jit(cqp_chunk_step)/and"}
  ROOT %copy.8 = f32[16]{0} copy(%fusion.2), metadata={op_name="jit(cqp_chunk_step)/cqp.frontier/or"}
}
"""


def test_hlo_scopes_name_each_op_by_its_innermost_cqp_scope():
    hlo = layers.hlo_scopes(HLO_TEXT)
    # its own op_name; the program's name is no scope
    assert hlo["select.1"] == "store" and hlo["copy.8"] == "frontier"
    assert hlo["hash.3"] == "drop" and "and.7" not in hlo and "a" not in hlo
    # no op_name at all: the computation it calls, else its producers
    assert hlo["fusion.2"] == "store"
    assert hlo["sort.4"] == hlo["gte.5"] == hlo["fusion.6"] == "drop"


def test_layer_metrics_read_the_ring_the_scopes_and_the_counter():
    cell = tiny("lj_sssp_pdrop.b1024")
    rec = harness.run_cell(cell, seed=2**31 + 11, seconds=1.0, trace=False,
                           t_start=time.perf_counter(), log=lambda _m: None)
    ring = [{"name": "ingest", "dur": 1500.0}, {"name": "ingest", "dur": 500.0},
            {"name": "dispatch", "dur": 9000.0}]
    out, sc = layers.layer_metrics(rec, _synthetic(), ring, HLO)
    assert out["ingest_ms.rate"] == pytest.approx(2.0 / len(rec["window"].batches))
    assert out["expand_device_ms.rate"] == pytest.approx(30.0)
    assert out["store_device_ms.rate"] == pytest.approx(6.0)
    assert out["drop_device_ms.rate"] == 0.0
    # the counter's share: rows recomputed, repairs included, over all rows
    assert 0.0 < out["active_edge_share.rate"] <= 100.0
    assert sc["count"] == 1


def test_active_edge_share_reads_nothing_without_the_counter():
    read = harness.reader("active_edge_share.rate")

    class Window:
        batches = [{"iters_run": 7, "active_edges": 70.0},
                   {"iters_run": 3, "active_edges": 30.0}]

    # the register sweep: 5 iterations over every row, 100 edges each
    rec = {"setup": {"initial": {"iters_run": 5, "active_edges": 500.0}},
           "window": Window()}
    assert read(rec) == pytest.approx(10.0)
    # a program without the counter
    rec["setup"]["initial"] = {"iters_run": 5, "scheduled": 40}
    assert read(rec) is None
