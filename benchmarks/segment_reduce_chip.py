"""Device time of the sweep's two in-edge reductions, three ways, on a TPU.

At the benchmark cells' shape (16 queries, 589,824 vertices, 2^23 edge
slots, 90% live, the ``lj_sssp`` graph), one message pass (min of
``cur[src] + w`` over each vertex's in-edges) and one frontier pass (OR of
``changed[src]``) are timed as:

* ``scatter``: ``segment_min`` / ``segment_max`` over the unsorted edge
  slots, as the sweep ran them before the destination order;
* ``sorted_scatter``: the same segment ops over the edges in destination
  order, with ``indices_are_sorted=True``;
* ``blocked``: the blocked run reduction of ``repro.core.segments``, as the
  sweep runs it now;

plus ``edge_order``, the once-a-sweep sort.  Every way must give the same
bits.  Prints one JSON line; exits 1 on a device other than a TPU unless
``--small`` (a CPU rehearsal at a tiny size).

    python benchmarks/segment_reduce_chip.py
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench.graphgen import powerlaw_edges  # noqa: E402
from repro.core import engine as eng  # noqa: E402
from repro.core import plan  # noqa: E402


def _time(fn, *args, reps: int) -> tuple[float, object]:
    """Median wall seconds of ``fn(*args)`` to a ready result, after one
    call that compiles."""
    out = jax.block_until_ready(fn(*args))
    runs = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        runs.append(time.perf_counter() - t)
    return statistics.median(runs), out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--small", action="store_true", help="tiny CPU rehearsal")
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.small:
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
    q, v, e = (4, 1000, 1 << 13) if args.small else (16, 589_824, 1 << 23)
    rng = np.random.default_rng(2208002730)
    live = int(e * 0.9)
    src, dst, w = powerlaw_edges(v, live, 1.2, rng)
    pad = e - live
    g = eng.GraphArrays(
        src=jnp.asarray(np.concatenate([src, rng.integers(0, v, pad)]), jnp.int32),
        dst=jnp.asarray(np.concatenate([dst, rng.integers(0, v, pad)]), jnp.int32),
        weight=jnp.asarray(np.concatenate([w, np.ones(pad, np.float32)])),
        valid=jnp.asarray(np.arange(e) < live),
        out_degree=jnp.zeros(v, jnp.int32),
        in_degree=jnp.zeros(v, jnp.int32),
    )
    cur = rng.integers(0, 25, (q, v)).astype(np.float32)
    cur[rng.random((q, v)) < 0.5] = np.inf
    cur = jnp.asarray(cur)
    changed = jnp.asarray(rng.random((q, v)) < 0.01)
    cfg = eng.EngineConfig(
        num_queries=q, num_vertices=v, max_iters=64, semiring=plan.sssp(0).semiring
    )
    sr = cfg.semiring

    order_fn = jax.jit(
        lambda g: eng.edge_order(g.src, g.dst, g.valid, g.weight, v)
    )
    t_order, order = _time(order_fn, g, reps=args.reps)

    def sorted_scatter(g):
        key = jnp.where(g.valid, g.dst, v)
        key, perm = jax.lax.sort((key, jnp.arange(e, dtype=jnp.int32)), num_keys=1)
        s, wt = g.src[perm], g.weight[perm]
        seg = lambda x, op: jax.vmap(  # noqa: E731
            lambda r: op(r, key, num_segments=v, indices_are_sorted=True)
        )(x)
        agg = seg(sr.msg(cur[:, s], wt[None, :]), jax.ops.segment_min)
        hit = seg(changed[:, s].astype(jnp.int32), jax.ops.segment_max) > 0
        return jnp.minimum(agg, cur), hit

    def scatter_frontier(m):
        hit = (m[:, g.src] & g.valid[None, :]).astype(jnp.int32)
        return jax.vmap(lambda h: jax.ops.segment_max(h, g.dst, num_segments=v))(hit) > 0

    ways = {
        "scatter": (
            jax.jit(lambda c: eng.ife_step(cfg, c, g)),
            jax.jit(scatter_frontier),
        ),
        "blocked": (
            jax.jit(lambda c, o: eng.ife_step(cfg, c, g, order=o)),
            jax.jit(eng.push_frontier),
        ),
    }
    out = {"device": dev.device_kind, "platform": dev.platform, "shape":
           {"queries": q, "vertices": v, "edge_slots": e, "live": live},
           "edge_order_s": t_order}
    results = {}
    for name, (expand, frontier) in ways.items():
        extra = (order,) if name == "blocked" else ()
        te, new = _time(expand, cur, *extra, reps=args.reps)
        tf, hit = _time(frontier, changed, *extra, reps=args.reps)
        out[f"{name}_expand_s"], out[f"{name}_frontier_s"] = te, tf
        results[name] = (np.asarray(new), np.asarray(hit))
    # the sorted scatter times its own sort inside: report both passes whole
    t_ss, (new, hit) = _time(jax.jit(sorted_scatter), g, reps=args.reps)
    out["sorted_scatter_both_s"] = t_ss
    results["sorted_scatter"] = (np.asarray(new), np.asarray(hit))
    ref_new, ref_hit = results["scatter"]
    out["identical"] = {
        k: bool(np.array_equal(n, ref_new) and np.array_equal(h, ref_hit))
        for k, (n, h) in results.items()
    }
    print(json.dumps(out))
    return 0 if all(out["identical"].values()) else 1


if __name__ == "__main__":
    raise SystemExit(main())
