"""The command line: what it refuses, and BENCHMARK.json's shape."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
import types

import pytest

from bench import harness, run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _fake_devices(monkeypatch, kind, platform="tpu", count=1):
    import jax

    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev] * count)


def test_device_kind_outside_the_peaks_table_is_an_error(monkeypatch):
    _fake_devices(monkeypatch, "TPU v99")
    with pytest.raises(SystemExit, match="not in bench/peaks.json"):
        run.device_or_exit(1)


def test_too_few_chips_or_no_tpu_is_an_error(monkeypatch):
    _fake_devices(monkeypatch, "TPU v5 lite")
    assert len(run.device_or_exit(1)) == 1
    with pytest.raises(SystemExit, match="needs 4 chips"):
        run.device_or_exit(4)
    _fake_devices(monkeypatch, "cpu", platform="cpu")
    with pytest.raises(SystemExit, match="needs a TPU"):
        run.device_or_exit(1)


def _run_cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "lj_sssp.b1024", "--seed",
         str(2**31 + 1), "--seconds", "1", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_command_on_the_cpu_prints_no_result():
    p = _run_cli(harness.ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_command_alone_in_a_directory_prints_no_result(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_benchmark_json_keeps_the_contract():
    raw = (harness.ROOT / "BENCHMARK.json").read_bytes()
    assert len(raw) <= 64 * 1024
    b = json.loads(raw)
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"] and 1 <= len(b["command"]) <= 32
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in b[k]]
    assert all(NAME.match(n) for n in names) and len(set(names)) == len(names)
    shards = {}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and (harness.ROOT / c["file"]).is_file()
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        cfg = json.loads((harness.ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
        shards[c["name"]] = cfg.get("shards", 1)
    pairs = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        # a cell runs on as many chips as its configuration has shards
        assert w["chips"] == shards[w["config"]]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in SOURCES and m["moves"] in e2e
        moved = e2e[m["moves"]].get("workloads", [w["name"] for w in b["workloads"]])
        assert set(m["workloads"]) <= set(moved)
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for w in b["workloads"]:
        cell = harness.cell_spec(w["name"], b)
        assert "setup_s" in [m["name"] for m in cell["end_to_end"]]
        assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
