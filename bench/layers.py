"""Per-layer numbers that need the program's own names on the device trace,
from one run of one cell.

    python3 bench/layers.py --workload lj_sssp.b1024 --seed 7 --seconds 51 \\
        --cost-pairs 1 --events layers.json.gz

Sets the cell up as ``run.py`` does, then drives ``--cost-pairs`` pairs of
windows, one with tracing off and one with ``repro.obs.Tracer(profiler=True)``
installed and the profiler off (what tracing costs), and last one window
under the profiler with the tracer installed.  From that window it reads:

* ``ingest_ms.rate``: the program's ``ingest`` spans (the tracer's ring
  buffer), summed a batch, mean over the batches;
* ``expand_device_ms.rate``, ``store_device_ms.rate``, ``drop_device_ms.rate``:
  device time a run of the chunk program (``jit_cqp_chunk_step``) of the
  leaf ops in the ``cqp.expand`` and ``cqp.frontier`` scopes, in
  ``cqp.store``, and in ``cqp.drop``;
* ``active_edge_share.rate``: ``bench/metrics/active_edge_share.py``;
* ``device_scopes``: those device seconds by scope, ``unscoped`` the rest;
* ``idle_gaps``: device idle split among the innermost ``bench.*`` or
  ``cqp.*`` span over it.

An op's scope is the innermost ``cqp.*`` name in its ``op_name`` metadata,
read from the compiled chunk program's HLO by the op's name: a v5e trace
event carries no metadata, only its name and times.  Prints one JSON line,
with the cell's own per-layer metrics and the comparison's checks beside
these.  ``--events`` writes the traced window's device ops and host spans,
and each op's scope, for a look by hand.  Needs the cell's chips.
"""

from __future__ import annotations

import argparse
import bisect
import collections
import gzip
import json
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness, loadgen, run, trace_reduce  # noqa: E402

CHUNK_PROGRAM = "jit_cqp_chunk_step"
# the cqp.<phase> names in an op's name scope
SCOPE = re.compile(r"(?:^|/)cqp\.([a-z_]+)(?=/|$)")
# one HLO instruction: its name, and the rest of its line
HLO_LINE = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$")
# a computation's header
HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) ")
# ops that hold other ops: their time is their body's, counted there
CONTAINERS = ("while", "call", "conditional")
SPAN_PREFIXES = ("bench.", "cqp.")
# device-time metrics: the scopes each one sums
SCOPE_METRICS = {
    "expand_device_ms.rate": ("expand", "frontier"),
    "store_device_ms.rate": ("store",),
    "drop_device_ms.rate": ("drop",),
}


def _operands(rest: str) -> list:
    """The ``%name`` operands of one instruction, from what follows ``=``."""
    depth, i = 0, rest.find(" ")
    if rest.startswith("("):  # a tuple shape, with spaces: skip it whole
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
    start = rest.find("(", i + 1)
    depth = 0
    for j in range(start, len(rest)):
        depth += (rest[j] == "(") - (rest[j] == ")")
        if depth == 0:
            return re.findall(r"%([\w.\-]+)", rest[start:j])
    return []


def hlo_scopes(text: str) -> dict:
    """Instruction name → innermost ``cqp.*`` scope of its ``op_name``, from
    a compiled program's HLO text.  XLA leaves some instructions without
    an ``op_name`` (the sort and scatter it rewrites a scatter into, fusions
    of such): these take the scope most instructions of the computation
    they call carry, else that of their nearest producer that has one.  An
    instruction whose ``op_name`` names no scope stays out."""
    own, calls, operands, named = {}, {}, {}, set()
    members: dict = collections.defaultdict(collections.Counter)
    comp = None
    for line in text.splitlines():
        m = HLO_LINE.match(line)
        if m is None:
            h = HLO_COMPUTATION.match(line)
            comp = h.group(1) if h else comp
            continue
        name, rest = m.groups()
        operands[name] = _operands(rest)
        op_name = re.search(r'op_name="([^"]*)"', rest)
        if op_name:
            named.add(name)
        found = SCOPE.findall(op_name.group(1)) if op_name else []
        if found:
            own[name] = found[-1]
            members[comp][found[-1]] += 1
        called = re.search(r"calls=%([\w.\-]+)", rest)
        if called:
            calls[name] = called.group(1)

    def direct(name):
        if name in own:
            return own[name]
        inner = members.get(calls.get(name))
        return inner.most_common(1)[0][0] if inner else None

    out = dict(own)
    for name in operands.keys() - named:
        frontier, seen = [name], {name}
        while frontier and name not in out:
            for n in frontier:
                if direct(n):
                    out[name] = direct(n)
                    break
            frontier = [o for n in frontier for o in operands.get(n, ()) if o not in seen]
            seen.update(frontier)
    return out


def chunk_program_hlo(session) -> str:
    """HLO text of the session's compiled chunk program (a compile-cache
    hit: the shapes are those of every chunk)."""
    from repro.obs.probes import _dense_impl

    impl = _dense_impl(session)
    upd = impl._encode_chunk([], [], impl.batch_capacity)
    return impl._step.lower(impl.state, impl.g, upd).compile().as_text()


def _first_device(events) -> str | None:
    devices = sorted({e[0] for e in events if trace_reduce.DEVICE_PLANE.match(e[0])})
    return devices[0] if devices else None


def scopes(events, hlo: dict, module: str = CHUNK_PROGRAM) -> dict:
    """Device seconds of the leaf ops that ran inside ``module``'s runs on
    the first device, by the scope ``hlo`` gives their name (``unscoped``
    the rest), with the module's run count and device seconds."""
    dev = _first_device(events)
    runs = sorted((s, s + d) for (p, ln, n, s, d) in events
                  if p == dev and ln == trace_reduce.MODULES_LINE and n.split("(")[0] == module)
    starts = [a for a, _b in runs]
    by_scope: collections.Counter = collections.Counter()
    for (p, ln, n, s, d) in events:
        # "%fusion.80 = f32[...] fusion(...)": the op's name alone
        name = n.split(" = ")[0].lstrip("%")
        if p != dev or ln != trace_reduce.OPS_LINE or name.split(".")[0] in CONTAINERS:
            continue
        k = bisect.bisect_right(starts, s) - 1
        if k >= 0 and s < runs[k][1]:
            by_scope[hlo.get(name, "unscoped")] += d * 1e-9
    return {"count": len(runs), "module_s": sum(b - a for a, b in runs) * 1e-9,
            "scopes": dict(by_scope)}


def idle_gaps(events, top: int = 12) -> list:
    """Idle time of the first device in the ``bench.window`` span, each
    piece given to the innermost ``bench.*`` or ``cqp.*`` host span over it
    (``host`` where none is): ``trace_reduce``'s split, with the program's
    spans beside the harness's."""
    (w0, w1), = [(s, s + d) for (p, ln, n, s, d) in events
                 if n == trace_reduce.WINDOW and not trace_reduce.DEVICE_PLANE.match(p)]
    spans = sorted((s, s + d, n) for (p, ln, n, s, d) in events
                   if n.startswith(SPAN_PREFIXES) and n != trace_reduce.WINDOW
                   and not trace_reduce.DEVICE_PLANE.match(p))
    dev = _first_device(events)
    if dev is None:
        return []
    ops = [(max(s, w0), min(s + d, w1)) for (p, ln, n, s, d) in events
           if p == dev and ln == trace_reduce.OPS_LINE and s < w1 and s + d > w0]
    u = trace_reduce._union(np.asarray(ops, np.float64).reshape(-1, 2))
    gaps = trace_reduce._idle_by_span(u, w0, w1, spans)
    return [[n, s] for n, s in gaps.most_common(top)]


def layer_metrics(rec: dict, events, ring: list, hlo: dict) -> tuple[dict, dict]:
    """The five per-layer numbers of one traced window, and the chunk
    program's scope breakdown."""
    out = {}
    batches = rec["window"].batches
    if batches:
        ingest = sum(e["dur"] for e in ring if e["name"] == "ingest") * 1e-3
        out["ingest_ms.rate"] = ingest / len(batches)
    sc = scopes(events, hlo)
    if sc["count"]:
        for name, phases in SCOPE_METRICS.items():
            out[name] = sum(sc["scopes"].get(p, 0.0) for p in phases) / sc["count"] * 1e3
    share = harness.reader("active_edge_share.rate")(rec)
    if share is not None:
        out["active_edge_share.rate"] = share
    return out, sc


def measure(cell: dict, *, seed: int, seconds: float, cost_pairs: int = 0,
            events_path: str | None = None, t_start: float | None = None) -> dict:
    """Set ``cell`` up, drive the cost windows and the traced window, and
    return what ``main`` prints."""
    from repro.obs import trace as obs_trace

    t_start = time.perf_counter() if t_start is None else t_start
    cr = harness.CellRun(cell, seed=seed, t_start=t_start)
    rates: dict = {"off": [], "on": []}
    try:
        for _ in range(cost_pairs):
            for mode in ("off", "on"):
                obs_trace.set_tracer(obs_trace.Tracer(profiler=True) if mode == "on" else None)
                rates[mode].append(loadgen.update_rate(cr.window(seconds)))
        tracer = obs_trace.set_tracer(obs_trace.Tracer(profiler=True))
        tracedir = tempfile.mkdtemp(prefix="bench-layers-")
        try:
            w = cr.window(seconds, tracedir=tracedir)
            obs_trace.set_tracer(None)
            events = trace_reduce.load(tracedir)
            hlo = hlo_scopes(chunk_program_hlo(cr.served.session))
            rec = cr.finish(w, tracedir)
        finally:
            shutil.rmtree(tracedir, ignore_errors=True)
    finally:
        obs_trace.set_tracer(None)
    layers, sc = layer_metrics(rec, events, tracer.events(), hlo)
    leaf_s = sum(sc["scopes"].values())
    scoped = leaf_s - sc["scopes"].get("unscoped", 0.0)
    if events_path:
        keep = [e for e in events if trace_reduce.DEVICE_PLANE.match(e[0])
                or e[2].startswith(SPAN_PREFIXES)]
        with gzip.open(events_path, "wt") as f:
            json.dump({"events": keep, "hlo_scopes": hlo}, f)
    return {
        "workload": cell["name"],
        "seed": seed,
        "correct": all(c["value"] <= c["limit"] for c in rec["checks"].values()),
        "update_rate": {**rates, "traced": loadgen.update_rate(w)},
        "layers": layers,
        "per_layer": harness.metrics(cell, rec, trace=True),
        "chunk_program": {"name": CHUNK_PROGRAM, "count": sc["count"],
                          "device_s": sc["module_s"], "leaf_s": leaf_s,
                          "scoped_share": scoped / leaf_s if leaf_s else None},
        "device_scopes": sc["scopes"],
        "idle_gaps": idle_gaps(events),
        "modules": rec["trace"]["modules"],
        "checks": rec["checks"],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--cost-pairs", type=int, default=0)
    ap.add_argument("--events", help="write the traced window's events here (.json.gz)")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    cell = harness.cell_spec(args.workload)
    devices = run.device_or_exit(cell["chips"])
    run.use_cache()
    out = measure(cell, seed=args.seed, seconds=args.seconds, cost_pairs=args.cost_pairs,
                  events_path=args.events, t_start=t_start)
    out["device"] = {"kind": devices[0].device_kind, "count": len(devices)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
