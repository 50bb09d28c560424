"""The benchmark's general traffic generator.

A traffic file (``bench/traffic/<name>.json``) names a ``loop`` and its
parameters; this module drives either loop against a ``process`` callback
that applies one batch of updates and returns when their answers are on the
host.  Both loops are single-threaded: the schedule of an open loop is fixed
before the window opens, so it does not slow when the system slows.

* ``closed``: one chunk of ``chunk`` updates in flight; the next is sent
  when the previous chunk's answers are read.  The window closes after the
  chunk that is running when ``seconds`` have passed, counted whole.
* ``poisson``: single-update arrivals at ``rate_per_s``, with exponential
  gaps drawn from the seed.  Whenever arrivals are due, all of them are
  drained into one batch; otherwise the loop sleeps until the next is due.
  Every arrival due inside ``seconds`` is served, and its latency runs from
  its due time.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable

import numpy as np
from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class Window:
    """What one measured window did, on the host clock (seconds from its
    start)."""

    batches: list = dataclasses.field(default_factory=list)  # process() records
    sizes: list = dataclasses.field(default_factory=list)  # updates per batch
    done_s: list = dataclasses.field(default_factory=list)  # batch answers read
    latency_s: list = dataclasses.field(default_factory=list)  # per arrival
    attempted: int = 0
    end_s: float = 0.0
    compile_s: float = 0.0  # compiling inside the window


def closed_loop(process: Callable, take: Callable, params: dict, seconds: float,
                clock=time.perf_counter) -> Window:
    w = Window()
    t0 = clock()
    while clock() - t0 < seconds:
        batch = take(int(params["chunk"]))
        w.batches.append(process(batch))
        w.sizes.append(len(batch))
        w.done_s.append(clock() - t0)
    w.attempted = sum(w.sizes)
    w.end_s = w.done_s[-1] if w.done_s else clock() - t0
    return w


def arrivals(rate_per_s: float, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """Due times of a Poisson process at ``rate_per_s`` over ``[0, seconds)``:
    exponential gaps drawn from ``rng``."""
    n = int(rate_per_s * seconds) + 1
    due = np.cumsum(rng.exponential(1.0 / rate_per_s, n))
    while due[-1] < seconds:
        due = np.concatenate([due, due[-1] + np.cumsum(rng.exponential(1.0 / rate_per_s, n))])
    return due[due < seconds]


def poisson_loop(process: Callable, take: Callable, params: dict, seconds: float,
                 rng: np.random.Generator, clock=time.perf_counter,
                 sleep=time.sleep) -> Window:
    due = arrivals(float(params["rate_per_s"]), seconds, rng)
    w = Window(attempted=int(due.size))
    t0 = clock()
    i = 0
    while i < due.size:
        now = clock() - t0
        j = int(np.searchsorted(due, now, side="right"))
        if j == i:
            with TraceAnnotation("bench.wait"):
                sleep(due[i] - now)
            continue
        w.batches.append(process(take(j - i)))
        done = clock() - t0
        w.sizes.append(j - i)
        w.done_s.append(done)
        w.latency_s.extend((done - due[i:j]).tolist())
        i = j
    w.end_s = clock() - t0
    return w


LOOPS = {"closed": closed_loop, "poisson": poisson_loop}


def update_rate(w: Window) -> float:
    """Updates applied over all the time the window's batches took."""
    return sum(w.sizes) / w.end_s


def percentile_ms(samples_s, q: float) -> float:
    return float(np.percentile(np.asarray(samples_s, np.float64), q)) * 1e3
