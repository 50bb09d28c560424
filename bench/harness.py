"""One benchmark cell, run once, and what it measured.

``run_cell`` builds the configuration's graph, with labels, orders and an
update stream drawn from the seed, loads the graph into the program (``DynamicGraph``, ``CQPSession``), registers the
queries, runs one warm-up batch, measures the traffic for the window, and
then compares the answers with the plain reference on the harness's own
live-edge set.  ``bench/run.py`` is the command line around it.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name that ``BENCHMARK.json`` gives it:
``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``,
``bench/metrics/<metric>.py`` and ``bench/reference/<family>.py``.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

from bench import loadgen, trace_reduce  # noqa: E402
from bench.graphgen import EdgeStream, powerlaw_edges  # noqa: E402

# jax.monitoring events whose durations make up a program's compile time
# (trace, lowering, and the backend compile or persistent-cache load)
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)

# independent random streams: GRAPH and SPLIT from the configuration's fixed
# graph_seed, the others from --seed
GRAPH, SPLIT, LABELS, ORDER, UPDATES, ARRIVALS, SAMPLE = range(7)


class CompileClock:
    """Seconds JAX spends compiling (or loading from the persistent cache)
    while registered."""

    def __init__(self) -> None:
        self.seconds = 0.0

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event in COMPILE_EVENTS:
            self.seconds += duration

    def __enter__(self) -> "CompileClock":
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def _load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------------ the cell
def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def shards(config: dict, chips: int | None = None) -> int:
    """The number of chips the configuration's vertices are sharded over:
    its ``shards``, or 1 where it has none.  Raises where they do not
    divide ``num_vertices``, or differ from the cell's ``chips``."""
    n, v = int(config.get("shards", 1)), int(config["num_vertices"])
    if n < 1 or v % n:
        raise ValueError(f"shards {n} must be at least 1 and divide num_vertices {v}")
    if chips is not None and chips != n:
        raise ValueError(f"the cell asks for {chips} chips, and its configuration "
                         f"shards its vertices over {n}: they must be equal")
    return n


def cell_spec(name: str, bench: dict | None = None, root: Path = ROOT) -> dict:
    """The cell ``name`` with its configuration, traffic and metrics."""
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((root / entry["file"]).read_text())
    shards(config, int(cell["chips"]))
    return {
        "name": name,
        "chips": int(cell["chips"]),
        "config": config,
        "traffic": json.loads(
            (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text()
        ),
        "end_to_end": [m for m in bench["end_to_end"] if _applies(m, name)],
        "per_layer": [m for m in bench["per_layer"] if _applies(m, name)],
    }


def reader(metric: str, metrics_dir: Path = BENCH / "metrics"):
    """The reader of ``metric``: ``metrics/<metric>.py``, or for a metric
    split by suffix (``answer_read_ms.rate``) the reader of its stem."""
    path = metrics_dir / f"{metric}.py"
    if not path.exists():
        path = metrics_dir / f"{metric.split('.')[0]}.py"
    return _load_module(path).read


def reference_for(family: str):
    return _load_module(BENCH / "reference" / f"{family}.py").reference


# ----------------------------------------------------------------- the graph
def build(config: dict, seed: int) -> tuple[EdgeStream, np.ndarray]:
    """The configuration's one graph and split, drawn from its fixed
    ``graph_seed``, under vertex labels and a load and insert order drawn
    from ``seed``; and the query sources.  Every seed serves the same
    graph, so no seed changes the work a sweep does."""
    if config["generator"] != "powerlaw":
        raise ValueError(f"unknown generator {config['generator']!r}")
    if config["sources"] != "top_out_degree":
        raise ValueError(f"unknown source rule {config['sources']!r}")
    v, fixed = int(config["num_vertices"]), int(config["graph_seed"])
    src, dst, weight = powerlaw_edges(
        v, int(config["num_edges"]), float(config["zipf_exponent"]), rng(fixed, GRAPH)
    )
    stream = EdgeStream(src, dst, weight, load_share=float(config["load_share"]),
                        split=rng(fixed, SPLIT), order=rng(seed, ORDER))
    # chosen before relabelling, so that no seed breaks a tie of degrees
    # another way
    sources = top_out_degree(stream, v, int(config["num_queries"]))
    label = rng(seed, LABELS).permutation(v).astype(np.int32)
    stream.src, stream.dst = label[src], label[dst]
    return stream, label[sources]


def top_out_degree(stream: EdgeStream, num_vertices: int, count: int) -> np.ndarray:
    """The ``count`` vertices of highest out-degree in the loaded graph."""
    deg = np.bincount(stream.src[stream.live], minlength=num_vertices)
    return np.argsort(-deg, kind="stable")[:count]


def degree_percentile(stream: EdgeStream, num_vertices: int, pct: float) -> float:
    """Percentile of in + out degree over the vertices that have an edge."""
    live = stream.live
    deg = np.bincount(stream.src[live], minlength=num_vertices) + np.bincount(
        stream.dst[live], minlength=num_vertices
    )
    return float(np.percentile(deg[deg > 0], pct))


def drop_config(config: dict, stream: EdgeStream):
    """The configuration's ``DropConfig``, with ``tau_max`` worked out from
    the loaded graph where the configuration names a degree percentile."""
    from repro.core import dropping as dr

    spec = config.get("drop")
    if not spec:
        return None
    spec = dict(spec)
    pct = spec.pop("tau_max_degree_percentile", None)
    if pct is not None:
        spec["tau_max"] = degree_percentile(stream, int(config["num_vertices"]), pct)
    return dr.DropConfig(**spec)


# ------------------------------------------------------------------- serving
class Served:
    """The program under test: one session over the loaded graph, and what
    each batch of updates did to it.  A configuration with ``shards`` runs
    the session's vertex-sharded dense path over that many devices."""

    def __init__(self, config: dict, traffic: dict, stream: EdgeStream, drop) -> None:
        from repro.core.graph import DynamicGraph
        from repro.core.session import CQPSession
        from repro.launch.mesh import make_data_mesh

        v = int(config["num_vertices"])
        self.shards = shards(config)
        self.mesh = make_data_mesh(self.shards) if "shards" in config else None
        ids = stream.loaded()
        edges = list(zip(stream.src[ids].tolist(), stream.dst[ids].tolist(),
                         stream.weight[ids].tolist()))
        self.batch = int(traffic.get("batch_size", traffic.get("chunk")))
        self.max_iters = int(config["max_iters"])
        t = time.perf_counter()
        self.graph = DynamicGraph(v, edges, capacity=stream.num_edges)
        self.session = CQPSession(
            self.graph,
            engine="dense",
            mode=config["mode"],
            backend=config["backend"],
            drop=drop,
            batch_capacity=self.batch,
            min_slots=int(config["num_queries"]),
            mesh=self.mesh,
        )
        self.load_s = time.perf_counter() - t
        self.handles = []
        self.latest = None

    def register(self, plans) -> dict:
        self.handles = self.session.register_many(plans)
        self.latest = self.session.answers_snapshot()
        return _stats(self.session.last_stats)

    def process(self, updates) -> dict:
        """Apply one drained batch, then read every query's answers."""
        with TraceAnnotation("bench.apply"):
            stats = self.session.apply_updates_batched(updates, batch_size=self.batch)
        t = time.perf_counter()
        with TraceAnnotation("bench.snapshot"):
            self.latest = self.session.answers_snapshot()
        out = _stats(stats)
        out["read_s"] = time.perf_counter() - t
        out["updates"] = len(updates)
        out["chunks"] = -(-len(updates) // self.batch)
        # the summed sweeps of one call hit the bound only if one of them did
        out["capped"] = out["iters_run"] >= self.max_iters * out["chunks"]
        return out

    def answers(self, snap: dict) -> np.ndarray:
        return np.stack([snap[h.qid] for h in self.handles]).astype(np.float64)

    def close(self) -> None:
        self.session = self.graph = None
        gc.collect()


    def devices(self) -> list:
        """The devices that hold the session's state."""
        return list(self.mesh.devices.flat) if self.mesh is not None else jax.devices()[:1]


def device_peak(stats: list) -> tuple[int | None, list]:
    """The fullest device's peak bytes, and each device's, from their
    ``memory_stats()``: the allocator's peak of buffers in use, plus the
    peak that loaded programs reserve for their temporaries and code, which
    a TPU keeps apart at the bottom of its memory.  ``(None, [])`` where a
    device reports no statistics (the CPU)."""
    if not stats or not all(stats):
        return None, []
    peaks = [s["peak_bytes_in_use"] + s["peak_bytes_reserved"] for s in stats]
    return max(peaks), peaks


def _stats(stats) -> dict:
    return {k: int(getattr(stats, k)) for k in stats.SCALAR_FIELDS}


# ------------------------------------------------------------------ the run
def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CellRun:
    """One cell in one process: set-up on construction, then measured
    windows, then the comparison with the reference."""

    def __init__(self, cell: dict, *, seed: int, t_start: float,
                 control: bool = False, log=_log) -> None:
        from repro.core import plan as qp

        self.cell, self.seed, self.log, self.control = cell, seed, log, control
        config, traffic = cell["config"], cell["traffic"]
        self.num_vertices = int(config["num_vertices"])
        with TraceAnnotation("bench.build"):
            self.stream, self.sources = build(config, seed)
            drop = drop_config(config, self.stream)
        log(f"build: {time.perf_counter() - t_start:.3f} s, {self.stream.num_live} edges "
            f"loaded, sources {self.sources.tolist()}, drop {drop}")
        with TraceAnnotation("bench.load"):
            self.served = Served(config, traffic, self.stream, drop)
        builders = {"sssp": qp.sssp}
        plans = [builders[config["family"]](int(s), max_iters=self.served.max_iters, drop=drop)
                 for s in self.sources]
        self.setup: dict = {"graph_load_s": self.served.load_s}
        self._updates = rng(seed, UPDATES)
        self._delete_share = float(traffic["delete_share"])
        self._stale = None  # the live set one batch back (control runs)
        with CompileClock() as clock:
            t = time.perf_counter()
            with TraceAnnotation("bench.register"):
                self.setup["initial"] = self.served.register(plans)
            self.setup["initial_sweep_s"] = time.perf_counter() - t - clock.seconds
            self.setup["register_compile_s"] = clock.seconds
            with TraceAnnotation("bench.warmup"):
                self.setup["warmup"] = self.served.process(self.take(self.served.batch))
            self.setup["compile_s"] = clock.seconds
        log(f"register: {self.setup['initial_sweep_s']:.3f} s sweep + "
            f"{self.setup['register_compile_s']:.3f} s compile, {self.setup['initial']}; "
            f"warm-up {self.setup['warmup']}")
        # the answers checked besides the last: one batch drawn from the seed
        self._pick = rng(seed, SAMPLE)
        self.sample: dict = {}
        self.batches: list = []
        self.setup["setup_s"] = time.perf_counter() - t_start
        log(f"setup: {self.setup['setup_s']:.3f} s")

    def take(self, n: int) -> list:
        if self.control:
            self._stale = self.stream.live.copy()
        return self.stream.take(n, self._delete_share, self._updates)

    def process(self, batch: list) -> dict:
        out = self.served.process(batch)
        k = out["k"] = len(self.batches) + 1
        self.batches.append(out)
        if self._pick.random() * k < 1.0:
            self.sample.update(k=k, snap=self.served.latest, live=self.stream.live.copy())
        return out

    def window(self, seconds: float, tracedir: str | None = None) -> loadgen.Window:
        """Drive the traffic for ``seconds``; with ``tracedir``, under the
        profiler."""
        traffic = self.cell["traffic"]
        loop = loadgen.LOOPS[traffic["loop"]]
        kwargs = {"rng": rng(self.seed, ARRIVALS)} if traffic["loop"] == "poisson" else {}
        first = len(self.batches)
        if tracedir:
            # host spans are the harness's annotations; no Python call tracing
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tracedir, profiler_options=opts)
        with CompileClock() as clock:
            with TraceAnnotation("bench.window"):
                w = loop(self.process, self.take, traffic, seconds, **kwargs)
        if tracedir:
            jax.profiler.stop_trace()
        w.compile_s = clock.seconds
        self.log(f"window: {w.end_s:.3f} s, {len(w.batches)} batches, {sum(w.sizes)} "
                 f"updates, compile inside {clock.seconds:.3f} s")
        for b in self.batches[first:]:
            self.log(f"  batch {b['k']}: {b['updates']} updates, iters_run "
                     f"{b['iters_run']}, read {b['read_s'] * 1e3:.3f} ms, "
                     f"repairs {b['repairs']}")
        return w

    def finish(self, w: loadgen.Window, tracedir: str | None = None) -> dict:
        """Read the device, free the program's state, compare with the
        reference.  Returns the record the metric readers read, with the
        comparison's numbers under ``checks``."""
        served = self.served
        stats = [d.memory_stats() for d in served.devices()]
        self.log(f"memory_stats: {stats}")
        peak, peaks = device_peak(stats)
        rec = {
            "cell": self.cell["name"],
            "num_queries": len(self.sources),
            "shards": served.shards,
            "setup": self.setup,
            "window": w,
            "diff_bytes": served.session.nbytes(),
            "peak_bytes": peak,
            "peak_bytes_devices": peaks,
            "failed": sum(b["updates"] for b in w.batches if b["capped"]),
            "trace": None,
        }
        if tracedir:
            events = trace_reduce.load(tracedir)
            self.log(f"trace: {trace_reduce.summary(events)}")
            rec["trace"] = trace_reduce.reduce(events)
            self.log(f"trace modules: {rec['trace']['modules']}; busy per device "
                     f"{rec['trace']['busy_s_devices']} s, exposed collective "
                     f"{rec['trace']['collective_s']} s")
        final = served.answers(served.latest)
        sampled = None
        if self.sample and self.sample["k"] != self.batches[-1]["k"]:
            sampled = served.answers(self.sample["snap"])
        served.close()
        stream, v = self.stream, self.num_vertices
        reference = reference_for(self.cell["config"]["family"])

        def ref(live: np.ndarray) -> np.ndarray:
            return reference(v, stream.src[live], stream.dst[live], stream.weight[live],
                             self.sources)

        t = time.perf_counter()
        want = ref(stream.live)
        checks = {"wrong_final": {"value": int((final != want).sum()), "limit": 0}}
        if sampled is not None:
            got = ref(self.sample["live"])
            checks["wrong_sample"] = {"value": int((sampled != got).sum()), "limit": 0}
        if self.control:
            # the reference put in the program's place, one batch behind
            checks["control_stale"] = {
                "value": int((ref(self._stale) != want).sum()), "limit": 0}
        rec["reference_s"] = time.perf_counter() - t
        rec["largest_distance"] = float(want[np.isfinite(want)].max())
        self.log(f"reference: {rec['reference_s']:.3f} s; reached per query "
                 f"{np.isfinite(want).sum(axis=1).tolist()}; largest distance "
                 f"{rec['largest_distance']}")
        rec["checks"] = checks
        return rec


def run_cell(cell: dict, *, seed: int, seconds: float, trace: bool, t_start: float,
             control: bool = False, log=_log) -> dict:
    """Run ``cell`` once: set-up, one measured window, the comparison."""
    run = CellRun(cell, seed=seed, t_start=t_start, control=control, log=log)
    tracedir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        w = run.window(seconds, tracedir=tracedir)
        return run.finish(w, tracedir)
    finally:
        if tracedir:
            shutil.rmtree(tracedir, ignore_errors=True)


def metrics(cell: dict, rec: dict, trace: bool) -> dict:
    """The cell's end-to-end metrics (``trace`` off) or per-layer metrics
    (``trace`` on), each from its reader; a reader that finds nothing to
    read leaves its metric out."""
    out = {}
    for m in cell["per_layer" if trace else "end_to_end"]:
        value = reader(m["name"])(rec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
