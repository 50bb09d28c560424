"""sweep_device_ms: device time of one run of the chunk program, from the
profile.  The reader takes the program with the most device time in the
window, which is the chunk program (``jit_cqp_chunk_step``, or its
``_sharded`` twin) in every cell."""


def read(rec):
    modules = (rec["trace"] or {}).get("modules")
    if not modules:
        return None
    heaviest = max(modules.values(), key=lambda m: m["seconds"])
    return heaviest["seconds"] / heaviest["count"] * 1e3
