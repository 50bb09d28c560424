"""Chip smoke: the served CQP path end to end on TPU, checked against SciPy.

Deployment (the paper's §6.1 protocol on a LiveJournal-shaped graph): a
power-law graph of 2^20 vertices and 2^23 edges, 90% loaded and 10% streamed;
16 SSSP queries (JOD, ``max_iters=64``) from the 16 highest out-degree
vertices on a dense ``CQPSession``; one warm-up chunk, then 8 δE chunks of
1024 updates with a 10% deletion share through ``apply_updates_batched``.
Every answer is compared with ``scipy.sparse.csgraph.dijkstra`` on the final
live edge set.  ``--chips 4`` runs the same deployment vertex-sharded over a
4-device ``data`` mesh, and nothing else.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips, sharded state

Exits non-zero, before any work, when JAX's first device is not a TPU.  The
last line of a passing run is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from scipy.sparse import csr_matrix  # noqa: E402
from scipy.sparse.csgraph import dijkstra  # noqa: E402

from repro.core import plan  # noqa: E402
from repro.core.graph import DynamicGraph, edge_capacity  # noqa: E402
from repro.core.session import CQPSession  # noqa: E402
from repro.data.graphgen import powerlaw_graph, split_90_10, update_stream  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_data_mesh  # noqa: E402

# jax.monitoring events whose durations make up a program's compile time
# (trace, lowering, and the backend compile or persistent-cache load)
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


@dataclasses.dataclass(frozen=True)
class Deployment:
    num_vertices: int = 1 << 20
    num_edges: int = 1 << 23
    num_queries: int = 16
    max_iters: int = 64
    batch: int = 1024
    chunks: int = 8  # after the warm-up chunk
    delete_fraction: float = 0.1


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


def reference(graph: DynamicGraph, sources: np.ndarray) -> np.ndarray:
    """Dijkstra distances [Q, V] on the graph's live edges (inf unreachable)."""
    live = graph.valid
    src, dst = graph.src[live].astype(np.int64), graph.dst[live].astype(np.int64)
    v = graph.num_vertices
    if np.unique(src * v + dst).size != src.size:
        raise ValueError("parallel edges: csr_matrix would sum their weights")
    adj = csr_matrix((graph.weight[live].astype(np.float64), (src, dst)), shape=(v, v))
    return dijkstra(adj, directed=True, indices=sources)


def run(dep: Deployment, *, seed: int = 0, mesh=None, log=print) -> dict:
    """Build the deployment, serve it, and check every answer; raises on
    any failed check.  Returns the measurements."""
    out: dict = {}
    t0 = time.perf_counter()
    edges = powerlaw_graph(dep.num_vertices, dep.num_edges, seed=seed)
    initial, pool = split_90_10(edges, seed=seed)
    stream = update_stream(
        initial,
        dep.num_vertices,
        num_batches=1 + dep.chunks,
        batch_size=dep.batch,
        delete_fraction=dep.delete_fraction,
        insert_pool=pool,
        seed=seed + 1,
    )
    graph = DynamicGraph(
        dep.num_vertices,
        initial,
        capacity=edge_capacity(initial, [u for b in stream for u in b]),
    )
    sources = np.argsort(-graph.out_degree, kind="stable")[: dep.num_queries]
    out["host_build_s"] = time.perf_counter() - t0
    log(
        f"host build: {out['host_build_s']:.3f} s ({len(initial)} initial edges, "
        f"{graph.capacity} edge slots, {len(stream)} chunks of <= {dep.batch})"
    )

    session = CQPSession(
        graph, engine="dense", mesh=mesh, batch_capacity=dep.batch,
        min_slots=dep.num_queries,
    )
    plans = [plan.sssp(int(s), max_iters=dep.max_iters) for s in sources]

    def check_converged(what: str) -> int:
        iters = int(session.last_stats.iters_run)
        if iters >= dep.max_iters:
            raise RuntimeError(f"{what}: sweep ran {iters} >= max_iters iterations")
        return iters

    with CompileClock() as clock:
        t = time.perf_counter()
        handles = session.register_many(plans)
        answers = session.answers_snapshot()
        wall = time.perf_counter() - t
    out["compile_s"] = clock.seconds
    out["initial_sweep_s"] = wall - clock.seconds
    # the engine is built at the first registration, so the sweep's seconds
    # include uploading the graph to the device
    log(
        f"register {dep.num_queries} queries: compile {clock.seconds:.3f} s, "
        f"graph upload + initial sweep {out['initial_sweep_s']:.3f} s, "
        f"iters_run {check_converged('initial sweep')}"
    )

    out["chunk_s"] = []
    for k, chunk in enumerate(stream):
        with CompileClock() as clock:
            t = time.perf_counter()
            session.apply_updates_batched(chunk, batch_size=dep.batch)
            answers = session.answers_snapshot()
            wall = time.perf_counter() - t
        iters = check_converged(f"chunk {k}")
        if k == 0:
            out["warmup_compile_s"] = clock.seconds
            out["warmup_s"] = wall
            log(f"warm-up chunk: {wall:.3f} s (compile {clock.seconds:.3f} s), "
                f"iters_run {iters}")
        else:
            out["chunk_s"].append(wall)
            log(f"chunk {k}: {wall:.6f} s to answers on host "
                f"(compile {clock.seconds:.3f} s), {len(chunk)} updates, "
                f"iters_run {iters}")

    out["session_nbytes"] = session.nbytes()
    out["nbytes_per_device"] = session.nbytes_per_device()
    devices = list(mesh.devices.flat) if mesh is not None else jax.devices()[:1]
    out["peak_bytes_in_use"] = [
        (d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices
    ]
    log(f"session.nbytes() {out['session_nbytes']}; per device "
        f"{out['nbytes_per_device']}; peak_bytes_in_use {out['peak_bytes_in_use']}")

    t = time.perf_counter()
    ref = reference(graph, sources)
    out["reference_s"] = time.perf_counter() - t
    got = np.stack([answers[h.qid] for h in handles]).astype(np.float64)
    reached = np.isfinite(got).sum(axis=1)
    log(f"reached vertices per query: {reached.tolist()}")
    bad = int((got != ref).sum())
    log(f"answers vs scipy dijkstra: {got.size - bad}/{got.size} equal "
        f"(reference {out['reference_s']:.3f} s)")
    if bad:
        raise RuntimeError(f"{bad} answers differ from the Dijkstra reference")
    if (reached < 2).any():
        raise RuntimeError("a query reached no vertex beyond its source")
    out["reached"] = reached.tolist()
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: serve the deployment sharded over a 4-device mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}", file=sys.stderr)
        return 1
    print(f"device_kind {dev.device_kind}, {len(jax.devices())} device(s)")
    print(f"compile cache: {use_compile_cache()}")
    mesh = make_data_mesh(4) if args.chips == 4 else None
    run(Deployment(), seed=args.seed, mesh=mesh)
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
