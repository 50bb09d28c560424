"""device_idle: share of the traced window in which no operation ran on a
device, in percent.  On several devices it reads the mean of their busy
seconds (``busy_s``), not the busiest or the idlest."""


def read(rec):
    trace = rec["trace"]
    if not trace or not trace["devices"]:
        return None
    return (1.0 - trace["busy_s"] / trace["window_s"]) * 100.0
