"""sweep_iters: MaintainStats.iters_run per chunk program, over the window."""


def read(rec):
    batches = rec["window"].batches
    chunks = sum(b["chunks"] for b in batches)
    return sum(b["iters_run"] for b in batches) / chunks if chunks else None
