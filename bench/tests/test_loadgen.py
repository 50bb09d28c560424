"""Rate and percentile arithmetic: all work over all time, every sample
timed from its due time."""

from __future__ import annotations

import numpy as np
import pytest

from bench import loadgen


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += max(s, 0.0) + 0.001  # wakes one millisecond late


def test_closed_loop_counts_the_last_chunk_whole():
    clock = FakeClock()
    took = []

    def process(batch):
        clock.t += 3.0  # each chunk takes 3 s
        took.append(len(batch))
        return {"n": len(batch)}

    w = loadgen.closed_loop(process, lambda n: [0] * n, {"chunk": 10}, 10.0, clock=clock)
    # chunks start at 0, 3, 6 and 9 s; the fourth ends at 12 s, past the window
    assert w.sizes == [10, 10, 10, 10] and w.end_s == 12.0
    assert loadgen.update_rate(w) == 40 / 12.0


def test_poisson_loop_times_every_update_from_its_due_time():
    clock = FakeClock()
    rng_a, rng_b = np.random.default_rng(4), np.random.default_rng(4)
    due = loadgen.arrivals(5.0, 4.0, rng_b)

    def process(batch):
        clock.t += 0.5  # every batch takes half a second
        return {}

    w = loadgen.poisson_loop(process, lambda n: [0] * n, {"rate_per_s": 5.0}, 4.0,
                             rng_a, clock=clock, sleep=clock.sleep)
    assert w.attempted == due.size == sum(w.sizes) == len(w.latency_s)
    # replay: a batch drains what is due when it starts, finishes 0.5 s later
    t, i, lat = 0.0, 0, []
    while i < due.size:
        j = int(np.searchsorted(due, t, side="right"))
        if j == i:
            t = due[i] + 0.001
            continue
        t += 0.5
        lat.extend(t - due[i:j])
        i = j
    assert np.allclose(w.latency_s, lat)
    assert min(w.latency_s) >= 0.5
    p95 = loadgen.percentile_ms(w.latency_s, 95)
    assert p95 == pytest.approx(np.percentile(np.asarray(lat), 95) * 1e3)  # all samples


def test_arrivals_are_a_poisson_process_drawn_from_the_seed():
    a = loadgen.arrivals(200.0, 50.0, np.random.default_rng(0))
    again = loadgen.arrivals(200.0, 50.0, np.random.default_rng(0))
    b = loadgen.arrivals(200.0, 50.0, np.random.default_rng(1))
    assert (a == again).all() and a.size != b.size
    assert (np.diff(a) > 0).all() and 0.0 < a[0] and a[-1] < 50.0
    assert a.size == pytest.approx(10000, abs=400)  # Poisson: sd 100
    # exponential: the mean gap is 1 / rate and the median ln 2 of it
    assert np.mean(np.diff(a)) == pytest.approx(1 / 200.0, rel=0.03)
    assert np.median(np.diff(a)) == pytest.approx(np.log(2) / 200.0, rel=0.05)
