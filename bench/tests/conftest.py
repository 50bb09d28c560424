"""Every benchmark cell, cut to a size the CPU runs in seconds.  The cells
keep their deletion share."""

from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

CELLS = [w["name"] for w in harness.load_benchmark()["workloads"]]


def tiny(name: str, num_vertices: int = 3 << 10, num_edges: int = 1 << 13) -> dict:
    """Cell ``name`` at ``num_vertices`` (not a power of two, as in the
    configurations) and ``num_edges``, with 4 queries and 16-update chunks."""
    cell = harness.cell_spec(name)
    cell["config"].update(num_vertices=num_vertices, num_edges=num_edges, num_queries=4)
    if cell["config"]["drop"]:
        cell["config"]["drop"]["bloom_bits"] = 1 << 12
    cell["traffic"]["chunk"] = 16
    return cell
