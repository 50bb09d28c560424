"""Readings for the limits of the comparison: the program's, and the
control's, on several seeds in one process.

    python3 bench/control.py --workload lj_sssp.b1024 --seeds 11,12,13 --seconds 20

Each seed runs the cell as ``run.py`` does, with a window of ``--seconds``,
and then also compares the reference one batch behind (the control: the
configuration's read-your-writes guarantee broken) with the reference.
Prints one JSON line per seed: every number compared, the largest distance
and the batch sizes.  Needs the cell's chips, like ``run.py``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.cell_spec(args.workload)
    run.device_or_exit(cell["chips"])
    run.use_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        rec = harness.run_cell(cell, seed=seed, seconds=args.seconds, trace=False,
                               t_start=time.perf_counter(), control=True)
        w = rec["window"]
        print(json.dumps({
            "workload": args.workload,
            "seed": seed,
            "checks": rec["checks"],
            "largest_distance": rec["largest_distance"],
            "batches": len(w.sizes),
            "last_batch": w.sizes[-1],
            "failed": rec["failed"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
