"""setup_s: process start to the first measured update (host clock)."""


def read(rec):
    return rec["setup"]["setup_s"]
