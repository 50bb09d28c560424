"""trace_reduce on a synthetic trace and on a small recorded one."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import TraceAnnotation

from bench import trace_reduce

DEV, HOST = "/device:TPU:0", "/host:CPU"
MS = 1e6  # nanoseconds


def _ev(plane, line, name, start_ms, dur_ms):
    return (plane, line, name, start_ms * MS, dur_ms * MS)


def test_reduce_synthetic_trace():
    events = [
        _ev(HOST, "python", "bench.window", 10, 100),
        _ev(HOST, "python", "bench.apply", 10, 60),
        _ev(HOST, "python", "bench.snapshot", 70, 20),
        _ev(HOST, "python", "bench.wait", 90, 20),
        # device: two overlapping ops, one outside the window, one module
        _ev(DEV, "XLA Ops", "fusion.1", 20, 30),
        _ev(DEV, "XLA Ops", "fusion.2", 40, 20),
        _ev(DEV, "XLA Ops", "copy.3", 75, 5),
        _ev(DEV, "XLA Ops", "fusion.1", 200, 50),
        _ev(DEV, "XLA Modules", "jit_batched_step(12)", 20, 40),
    ]
    r = trace_reduce.reduce(events)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.045)  # [20, 60] and [75, 80]
    gaps = dict(r["idle_gaps"])
    # idle: [10, 20] and [60, 70] in apply, [70, 75] and [80, 90] in the
    # snapshot, [90, 110] in the generator's wait
    assert gaps == pytest.approx({"bench.apply": 0.02, "bench.snapshot": 0.015,
                                  "bench.wait": 0.02})
    assert dict(r["device_ops"]) == pytest.approx({"fusion.1": 0.03, "fusion.2": 0.02,
                                                   "copy.3": 0.005})
    assert r["modules"] == {"jit_batched_step": {"count": 1, "seconds": pytest.approx(0.04)}}
    # one chip: its busy time alone, and no collective
    assert r["devices"] == 1 and r["busy_s_devices"] == [pytest.approx(0.045)]
    assert r["collective_s"] == 0.0


def test_reduce_two_chips_busy_and_exposed_collective_time():
    dev1 = "/device:TPU:1"
    events = [
        _ev(HOST, "python", "bench.window", 0, 100),
        # chip 0: the all-gather overlaps a fusion for 10 ms and runs alone
        # for 10 ms, all inside a while loop that does not hide it
        _ev(DEV, "XLA Ops", "while.3", 5, 90),
        _ev(DEV, "XLA Ops", "fusion.1", 10, 30),
        _ev(DEV, "XLA Ops", "%all-gather.5 = f32[4,64] all-gather(f32[4,16] %x)", 30, 20),
        _ev(DEV, "XLA Ops", "fusion.2", 60, 10),
        # chip 1: an async all-gather, its done alone; an all-reduce cut by
        # the window's end; a collective after the window
        _ev(dev1, "XLA Ops", "fusion.1", 10, 20),
        _ev(dev1, "XLA Ops", "all-gather-start.5", 30, 2),
        _ev(dev1, "XLA Ops", "all-gather-done.5", 40, 15),
        _ev(dev1, "XLA Ops", "fusion.2", 60, 10),
        _ev(dev1, "XLA Ops", "all-reduce.7", 95, 10),
        _ev(dev1, "XLA Ops", "collective-permute.2", 150, 10),
    ]
    r = trace_reduce.reduce(events)
    assert r["devices"] == 2
    # chip 0: [5, 95]; chip 1: [10, 32], [40, 55], [60, 70], [95, 100]
    assert r["busy_s_devices"] == [pytest.approx(0.090), pytest.approx(0.052)]
    assert r["busy_s"] == pytest.approx(0.071)
    # exposed: chip 0 [40, 50]; chip 1 [30, 32], [40, 55], [95, 100]
    assert r["collective_s"] == pytest.approx((0.010 + 0.022) / 2)
    assert dict(r["device_ops"])["all-gather.5"] == pytest.approx(0.020)


def test_reduce_needs_one_window():
    with pytest.raises(RuntimeError):
        trace_reduce.reduce([_ev(DEV, "XLA Ops", "fusion.1", 0, 1)])


def test_reduce_recorded_trace(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with TraceAnnotation("bench.window"):
        with TraceAnnotation("bench.apply"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = trace_reduce.load(str(tmp_path))
    names = {n for (_p, _l, n, _s, _d) in events}
    assert {"bench.window", "bench.apply"} <= names
    r = trace_reduce.reduce(events)
    assert r["window_s"] > 0
    # the CPU has no TPU plane: nothing is counted as device time
    assert r["devices"] == 0 and r["busy_s"] == 0.0
