"""graph_load_s: seconds in the program's constructors, DynamicGraph and
CQPSession (host clock)."""


def read(rec):
    return rec["setup"]["graph_load_s"]
