"""update_rate: updates applied, with every answer read, over all the time of
the window's batches (host clock)."""

from bench import loadgen


def read(rec):
    w = rec["window"]
    return loadgen.update_rate(w) if w.sizes else None
