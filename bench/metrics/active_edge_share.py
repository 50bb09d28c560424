"""active_edge_share: MaintainStats.active_edges over the window, as a share
of the edges the dense sweep reads in the same iterations, in percent.

``active_edges`` sums, over iterations and queries, the live in-edges of the
vertex rows an iteration recomputes (``sched | repair``): what a sweep that
reads only those rows' edges would read.  The dense sweep reads every live
edge of every query in every iteration.  That count per iteration, Q times
the live edges, is read off the register sweep, which reruns every row of
every query in every iteration: its ``active_edges`` over its ``iters_run``.
The stream's net inserts move the live-edge count by well under 1% from
the load to the end of a window.  A program without the counter gives
nothing to read."""


def read(rec):
    initial = rec["setup"]["initial"]
    batches = rec["window"].batches
    if "active_edges" not in initial or not batches or not initial["iters_run"]:
        return None
    dense_per_iter = initial["active_edges"] / initial["iters_run"]
    iters = sum(b["iters_run"] for b in batches)
    if not iters or not dense_per_iter:
        return None
    return sum(b["active_edges"] for b in batches) / (iters * dense_per_iter) * 100.0
