"""diff_mib_per_query: session.nbytes() after the window, the accounted
difference bytes, in MiB per registered query.  Not allocated memory: the
dense stores stay allocated whatever is dropped."""


def read(rec):
    return rec["diff_bytes"] / 2**20 / rec["num_queries"]
