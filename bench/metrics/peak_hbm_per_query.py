"""peak_hbm_per_query: the device's peak after the run, buffers in use plus
what loaded programs reserved, in MiB per registered query."""


def read(rec):
    peak = rec["peak_bytes"]
    return None if peak is None else peak / 2**20 / rec["num_queries"]
