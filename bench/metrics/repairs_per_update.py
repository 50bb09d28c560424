"""repairs_per_update: MaintainStats.repairs (dropped differences
recomputed) over the updates of the window."""


def read(rec):
    batches = rec["window"].batches
    n = sum(b["updates"] for b in batches)
    return sum(b["repairs"] for b in batches) / n if n else None
