"""A configuration vertex-sharded over four devices, through the harness on
the CPU under host emulation (in a subprocess, since the device count is
fixed when JAX starts), and the per-device readings on fakes."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import pytest

from bench import harness
from bench.tests.conftest import tiny

SHARDED = textwrap.dedent("""
    import time

    import jax
    import pytest

    from bench import harness
    from bench.tests.conftest import tiny
    from bench.tests.test_harness import _altered_answer, _half_batch

    assert len(jax.devices()) == 4
    SEED = 2**31 + 11


    def cell(shards):
        c = tiny("lj_sssp.b1024")
        if shards:
            c["config"]["shards"] = c["chips"] = shards
        return c


    def run(c):
        return harness.run_cell(c, seed=SEED, seconds=1.5, trace=False,
                                t_start=time.perf_counter(), log=lambda _m: None)


    def answers(c, batches=5):
        # the same updates, batch by batch, whatever the window's length
        cr = harness.CellRun(c, seed=SEED, t_start=time.perf_counter(), log=lambda _m: None)
        for _ in range(batches):
            cr.process(cr.take(cr.served.batch))
        out = cr.served.answers(cr.served.latest)
        n, devices = cr.served.session.num_shards, cr.served.devices()
        cr.served.close()
        return out, n, devices


    rec = run(cell(4))
    assert rec["shards"] == 4, rec["shards"]
    assert rec["checks"]["wrong_final"] == {"value": 0, "limit": 0}, rec["checks"]
    assert rec["checks"].get("wrong_sample", {"value": 0})["value"] == 0, rec["checks"]
    assert rec["failed"] == 0 and rec["window"].attempted > 0
    assert rec["peak_bytes"] is None and rec["peak_bytes_devices"] == []  # the CPU's

    got, n, devices = answers(cell(4))
    want, n1, devices1 = answers(cell(None))
    assert (n, n1) == (4, 1) and devices == jax.devices() and devices1 == jax.devices()[:1]
    assert (got == want).all(), int((got != want).sum())

    for fault in (_half_batch, _altered_answer):
        with pytest.MonkeyPatch.context() as mp:
            fault(mp)
            wrong = run(cell(4))["checks"]["wrong_final"]["value"]
        assert wrong > 0, fault.__name__
    print("SHARDED-HARNESS-OK")
""")


def test_sharded_cell_through_the_harness_subprocess():
    """Four emulated devices: the sharded cell is correct, answers as the
    unsharded one does on the same updates, and the planted faults read
    wrong answers."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(harness.ROOT))
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    out = subprocess.run([sys.executable, "-c", SHARDED], cwd=harness.ROOT, env=env,
                         capture_output=True, text=True, timeout=280)
    assert out.returncode == 0, out.stdout + out.stderr[-4000:]
    assert "SHARDED-HARNESS-OK" in out.stdout


def _stats(in_use, reserved):
    return {"peak_bytes_in_use": in_use, "peak_bytes_reserved": reserved, "bytes_limit": 1 << 34}


def test_device_peak_is_the_fullest_device():
    stats = [_stats(10, 5), _stats(12, 9), _stats(20, 0), _stats(1, 1)]
    assert harness.device_peak(stats) == (21, [15, 21, 20, 2])
    assert harness.device_peak(stats[:1]) == (15, [15])
    # a device without statistics (the CPU) gives no peak at all
    assert harness.device_peak([None]) == (None, [])
    assert harness.device_peak([stats[0], None]) == (None, [])


def test_peak_hbm_per_query_counts_the_fullest_device_once_a_shard():
    read = harness.reader("peak_hbm_per_query")
    rec = {"peak_bytes": 3 * 2**30 + 12345, "num_queries": 64, "shards": 4}
    assert read(rec) == rec["peak_bytes"] * 4 / 2**20 / 64
    one = dict(rec, num_queries=16, shards=1)
    assert read(one) == one["peak_bytes"] / 2**20 / 16  # one chip: as it always read
    assert read(dict(rec, peak_bytes=None)) is None


def _bench_root(tmp_path, chips, **config):
    """A copy of BENCHMARK.json, the configurations and the traffic under
    ``tmp_path``, with ``lj_sssp.b1024`` on ``chips`` and its configuration
    updated by ``config``."""
    bench = harness.load_benchmark()
    for w in bench["workloads"]:
        if w["name"] == "lj_sssp.b1024":
            w["chips"] = chips
    for sub in ("configs", "traffic"):
        shutil.copytree(harness.BENCH / sub, tmp_path / "bench" / sub)
    path = tmp_path / "bench" / "configs" / "lj_sssp.json"
    path.write_text(json.dumps({**json.loads(path.read_text()), **config}))
    return bench


@pytest.mark.parametrize("chips,config,error", [
    (1, {"shards": 4}, "must be equal"),
    (4, {}, "must be equal"),
    (4, {"shards": 4, "num_vertices": 589826}, "divide num_vertices"),
    (4, {"shards": 0}, "divide num_vertices"),
])
def test_cell_spec_refuses_chips_unlike_the_shards(tmp_path, chips, config, error):
    bench = _bench_root(tmp_path, chips, **config)
    with pytest.raises(ValueError, match=error):
        harness.cell_spec("lj_sssp.b1024", bench, root=tmp_path)


def test_cell_spec_takes_chips_equal_to_the_shards(tmp_path):
    bench = _bench_root(tmp_path, 4, shards=4)
    cell = harness.cell_spec("lj_sssp.b1024", bench, root=tmp_path)
    assert cell["chips"] == 4 and harness.shards(cell["config"]) == 4
    # the other cell, unsharded, stays on one chip
    assert harness.cell_spec("lj_sssp_pdrop.b1024", bench, root=tmp_path)["chips"] == 1


def test_configuration_without_shards_builds_no_mesh():
    cell = tiny("lj_sssp.b1024")
    assert "shards" not in cell["config"]
    stream, _sources = harness.build(cell["config"], 2**31 + 7)
    served = harness.Served(cell["config"], cell["traffic"], stream, None)
    try:
        assert served.shards == 1 and served.mesh is None
        assert served.session.mesh is None
        assert served.devices() == jax.devices()[:1]
    finally:
        served.close()
