"""peak_hbm_per_query: the fullest device's peak after the run, buffers in
use plus what loaded programs reserved, in MiB per registered query.  On a
configuration sharded over ``shards`` devices each device holds a
``1/shards`` part of every query, and the fullest one decides when the
next query no longer fits, so the peak counts ``shards`` times."""


def read(rec):
    peak = rec["peak_bytes"]
    return None if peak is None else peak * rec["shards"] / 2**20 / rec["num_queries"]
