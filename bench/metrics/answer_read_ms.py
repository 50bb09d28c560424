"""answer_read_ms: mean host time of the blocking answers_snapshot after
each batch of the window."""


def read(rec):
    batches = rec["window"].batches
    return sum(b["read_s"] for b in batches) / len(batches) * 1e3 if batches else None
