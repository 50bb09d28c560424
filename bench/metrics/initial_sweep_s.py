"""initial_sweep_s: register_many and the first answers_snapshot, less the
compile seconds inside them (host clock)."""


def read(rec):
    return rec["setup"]["initial_sweep_s"]
