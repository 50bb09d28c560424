"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload lj_sssp.b1024 --seed 7 --seconds 40 --trace 0

Exits non-zero, and prints no result, when JAX's first device is not a TPU,
when JAX sees fewer chips than the cell asks for, or when the device kind is
not in ``bench/peaks.json``.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``
(with ``--trace 1`` also ``breakdown``), then ``checks``: each number the
comparison with the reference read, beside its limit.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def device_or_exit(chips: int):
    import jax

    devices = jax.devices()
    dev = devices[0]
    peaks = json.loads((harness.BENCH / "peaks.json").read_text())["kinds"]
    if dev.platform != "tpu":
        sys.exit(f"bench: needs a TPU, JAX found {dev.platform!r}")
    if len(devices) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX found {len(devices)}")
    if dev.device_kind not in peaks:
        sys.exit(f"bench: device kind {dev.device_kind!r} is not in bench/peaks.json")
    return devices


def use_cache() -> None:
    """The checkout's compile cache (or ``JAX_COMPILATION_CACHE_DIR``), with
    every program in it, small ones too, so that only a checkout's first run
    compiles."""
    import jax
    from repro.launch.compile_cache import use_compile_cache

    cache = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    print(f"compile cache: {cache}", file=sys.stderr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        sys.exit("bench: --seed must not be negative")

    cell = harness.cell_spec(args.workload)
    devices = device_or_exit(cell["chips"])
    use_cache()

    rec = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                           trace=bool(args.trace), t_start=T_START)
    dev = devices[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
        "memory_peak_bytes": rec["peak_bytes"],
    }
    out = {
        "correct": all(c["value"] <= c["limit"] for c in rec["checks"].values()),
        "attempted": rec["window"].attempted,
        "failed": rec["failed"],
        "metrics": harness.metrics(cell, rec, bool(args.trace)),
        "device": device,
    }
    if args.trace:
        tr = rec["trace"]
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        out["breakdown"] = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
    out["checks"] = rec["checks"]
    for name, c in rec["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
