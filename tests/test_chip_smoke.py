"""``chip_smoke.py``: refuses to run off the chip, and its check holds at a
small size.

The script's own ``main`` runs only on a TPU.  Its ``run`` is the whole
served path and its SciPy comparison; here it runs on the CPU at a graph
small enough for a test, with the deployment's query count and shape.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod
    try:
        spec.loader.exec_module(mod)
        yield mod
    finally:
        sys.modules.pop("chip_smoke", None)


def _run_script(cwd: Path) -> subprocess.CompletedProcess:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chip_smoke.py"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def _no_result(proc: subprocess.CompletedProcess) -> bool:
    return '"ok"' not in proc.stdout


def test_chip_smoke_refuses_a_cpu_backend():
    proc = _run_script(ROOT)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert _no_result(proc)


def test_chip_smoke_fails_without_the_repo(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    proc = _run_script(tmp_path)
    assert proc.returncode != 0
    assert _no_result(proc)


def test_chip_smoke_small_deployment_matches_dijkstra(chip_smoke):
    dep = chip_smoke.Deployment(
        num_vertices=1 << 12, num_edges=1 << 15, batch=64, chunks=3
    )
    lines: list[str] = []
    out = chip_smoke.run(dep, seed=3, log=lines.append)
    assert len(out["chunk_s"]) == dep.chunks
    assert len(out["reached"]) == dep.num_queries
    assert min(out["reached"]) > 1
    assert out["session_nbytes"] > 0
    assert any(f"{dep.num_queries * dep.num_vertices}/" in ln for ln in lines)
    json.dumps(out)  # every measurement is plain data
