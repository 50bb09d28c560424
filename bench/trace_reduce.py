"""From a profiler trace to device busy and idle time.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a
directory and flattens it to events; ``reduce`` works on those events alone,
so a test can hand it a synthetic trace.

* The window is the harness's ``bench.window`` span on the host.
* Busy time is the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), clipped
  to the window: each device's, and their mean.
* Exposed collective time, on each device, is the time in the window in
  which a collective op (``all-gather``, ``all-reduce``, ``reduce-scatter``,
  ``collective-permute``, ``all-to-all``, with their ``-start``/``-done``
  forms) ran and no other op did, other than the ``while``, ``call`` and
  ``conditional`` ops that hold the others; then the mean over devices.
* The idle time of the first device is split at every harness span
  boundary (``bench.*``, other than the window), and each piece goes to the
  innermost span that covers it, or to ``host`` where none does.
* Per-op and per-program device time come from the ``XLA Ops`` and
  ``XLA Modules`` lines.
"""

from __future__ import annotations

import collections
import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
SPAN_PREFIX = "bench."
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "collective-permute",
               "all-to-all")
# ops that hold other ops: their time is their body's, counted there
CONTAINERS = ("while", "call", "conditional")


def load(directory: str) -> list[tuple[str, str, str, float, float]]:
    """``(plane, line, name, start_ns, duration_ns)`` of every event in the
    trace under ``directory``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {directory}, found {paths}")
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        for line in plane.lines:
            for e in line.events:
                out.append((plane.name, line.name, e.name, float(e.start_ns),
                            float(e.duration_ns)))
    return out


def summary(events, names: int = 3) -> dict:
    """Events per plane and line, with the first few names of each line:
    what a reader of the log needs to see how a trace is laid out."""
    out: dict = {}
    for (p, ln, n, _s, _d) in events:
        line = out.setdefault(p, {}).setdefault(ln, {"events": 0, "names": []})
        line["events"] += 1
        if len(line["names"]) < names and n not in line["names"]:
            line["names"].append(n)
    return out


def _union(intervals: np.ndarray) -> np.ndarray:
    """Disjoint sorted union of ``[start, end]`` rows."""
    if not len(intervals):
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    out = [list(iv[0])]
    for a, b in iv[1:]:
        if a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return np.asarray(out)


def _busy_before(u: np.ndarray, x: np.ndarray) -> np.ndarray:
    """Busy time in ``(-inf, x]`` for each ``x``, from the disjoint sorted
    busy intervals ``u``."""
    if not len(u):
        return np.zeros_like(x)
    before = np.concatenate([[0.0], np.cumsum(u[:, 1] - u[:, 0])])
    k = np.searchsorted(u[:, 0], x, side="right") - 1
    inside = np.clip(x - u[k.clip(min=0), 0], 0, u[k.clip(min=0), 1] - u[k.clip(min=0), 0])
    return np.where(k >= 0, before[k.clip(min=0)] + inside, 0.0)


def _idle_by_span(u: np.ndarray, w0: float, w1: float, spans) -> dict:
    """Idle time of the window split at every span boundary, each piece
    given to the innermost span that covers it (``host`` where none does)."""
    pts = np.unique(np.clip([w0, w1] + [t for (s, e, _n) in spans for t in (s, e)], w0, w1))
    mid = (pts[:-1] + pts[1:]) / 2
    owner = np.full(mid.size, -1)
    # nested spans: a later start, or the same start and an earlier end, is inner
    order = sorted(range(len(spans)), key=lambda i: (spans[i][0], -spans[i][1]))
    for i in order:
        s, e, _n = spans[i]
        owner[(mid >= s) & (mid < e)] = i
    idle = np.diff(pts) - np.diff(_busy_before(u, pts))
    out: collections.Counter = collections.Counter()
    for i, t in zip(owner.tolist(), idle.tolist()):
        if t > 0:
            out[spans[i][2] if i >= 0 else "host"] += t * 1e-9
    return out


def _length(intervals: list) -> float:
    u = _union(np.asarray(intervals, np.float64).reshape(-1, 2))
    return float((u[:, 1] - u[:, 0]).sum())


def _exposed(ops: list) -> float:
    """Time in which a collective of ``ops`` (``(start, end, name)``) ran
    and no other leaf op did."""
    coll = [(a, b) for a, b, n in ops if n.startswith(COLLECTIVES)]
    other = [(a, b) for a, b, n in ops
             if not n.startswith(COLLECTIVES) and n.split(".")[0] not in CONTAINERS]
    return _length(coll + other) - _length(other) if coll else 0.0


def reduce(events, top: int = 10) -> dict:
    windows = [(s, s + d) for (p, ln, n, s, d) in events
               if n == WINDOW and not DEVICE_PLANE.match(p)]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW} span, found {len(windows)}")
    w0, w1 = windows[0]
    spans = sorted(
        ((s, s + d, n) for (p, ln, n, s, d) in events
         if n.startswith(SPAN_PREFIX) and n != WINDOW and not DEVICE_PLANE.match(p)),
    )
    devices = sorted({p for (p, *_r) in events if DEVICE_PLANE.match(p)})
    busy, exposed, gaps_by_span = [], [], collections.Counter()
    op_s, module_s, module_n = collections.Counter(), collections.Counter(), collections.Counter()
    for k, dev in enumerate(devices):
        # "%fusion.80 = f32[...] fusion(...)": the op's name alone
        ops = [(max(s, w0), min(s + d, w1), n.split(" = ")[0].lstrip("%"))
               for (p, ln, n, s, d) in events
               if p == dev and ln == OPS_LINE and s < w1 and s + d > w0]
        u = _union(np.asarray([(a, b) for a, b, _n in ops], np.float64).reshape(-1, 2))
        busy.append(float((u[:, 1] - u[:, 0]).sum()) * 1e-9)
        exposed.append(_exposed(ops) * 1e-9)
        for (p, ln, n, s, d) in events:
            if p != dev or s >= w1 or s + d <= w0:
                continue
            if ln == OPS_LINE:
                op_s[n.split(" = ")[0].lstrip("%")] += d * 1e-9
            elif ln == MODULES_LINE:
                name = n.split("(")[0]
                module_s[name] += d * 1e-9
                module_n[name] += 1
        if k == 0:
            gaps_by_span = _idle_by_span(u, w0, w1, spans)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "devices": len(devices),
        "busy_s": float(np.mean(busy)) if busy else 0.0,
        "busy_s_devices": busy,
        "collective_s": float(np.mean(exposed)) if exposed else 0.0,
        "device_ops": [[n, s] for n, s in op_s.most_common(top)],
        "idle_gaps": [[n, s] for n, s in gaps_by_span.most_common(top)],
        "modules": {n: {"count": module_n[n], "seconds": module_s[n]} for n in module_s},
    }
