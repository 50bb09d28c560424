"""Each cell of BENCHMARK.json, cut to a tiny size, end to end through the
harness on the CPU, and the faults that the comparison has to catch."""

from __future__ import annotations

import time

import jax
import numpy as np
import pytest

from bench import harness
from bench.tests.conftest import CELLS, tiny
from repro.core import engine as eng


def _run(cell, seed=2**31 + 3, seconds=1.5, **kw):
    return harness.run_cell(cell, seed=seed, seconds=seconds, trace=False,
                            t_start=time.perf_counter(), log=lambda _m: None, **kw)


def _poisson(cell: dict) -> dict:
    """``cell`` under the open loop: single updates drained into 16-update
    batches."""
    cell["traffic"] = {"loop": "poisson", "rate_per_s": 50.0, "batch_size": 16,
                       "delete_share": cell["traffic"]["delete_share"]}
    return cell


# each cell, and the open loop on the first configuration
CASES = [(w, "closed") for w in CELLS] + [(CELLS[0], "poisson")]


def _case(cell, loop, *size):
    c = tiny(cell, *size)
    return _poisson(c) if loop == "poisson" else c


@pytest.mark.parametrize("cell,loop", CASES)
def test_cell_end_to_end(cell, loop):
    tiny_cell = _case(cell, loop)
    rec = _run(tiny_cell)
    assert rec["checks"]["wrong_final"] == {"value": 0, "limit": 0}
    assert rec["checks"].get("wrong_sample", {"value": 0})["value"] == 0
    assert rec["failed"] == 0 and rec["window"].attempted > 0
    assert rec["window"].compile_s == 0.0  # every shape was warmed up
    got = harness.metrics(tiny_cell, rec, trace=False)
    # the CPU reports no peak bytes: every other end-to-end metric is there
    want = {m["name"] for m in tiny_cell["end_to_end"]} - {"peak_hbm_per_query"}
    assert set(got) == want and all(m["value"] > 0 for m in got.values())
    layer = harness.metrics(tiny_cell, rec, trace=True)
    # without a device trace the device readers find nothing to read
    assert not any(k.startswith(("sweep_device_ms", "device_idle")) for k in layer)
    assert {"graph_load_s", "compile_s", "initial_sweep_s", "diff_mib_per_query"} <= set(layer)


def test_every_metric_and_cell_file_is_there():
    bench = harness.load_benchmark()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.reader(m["name"]))
    for w in bench["workloads"]:
        cell = harness.cell_spec(w["name"], bench)
        assert callable(harness.reference_for(cell["config"]["family"]))
        assert cell["traffic"]["loop"] in ("closed", "poisson")


def test_every_seed_serves_the_same_graph():
    config = tiny(CELLS[0])["config"]
    (a, src_a), (b, src_b) = harness.build(config, 2**31 + 5), harness.build(config, 6)

    def shape(s):
        v = config["num_vertices"]
        live = s.live
        out = np.bincount(s.src[live], minlength=v)
        inn = np.bincount(s.dst[live], minlength=v)
        return sorted(zip(out[s.src[live]], inn[s.dst[live]], s.weight[live]))

    assert shape(a) == shape(b)  # one graph and split, under other labels
    assert not (a.src == b.src).all() and not (a.loaded() == b.loaded()).all()
    deg = lambda s, q: np.bincount(s.src[s.live], minlength=config["num_vertices"])[q]  # noqa: E731
    assert (deg(a, src_a) == deg(b, src_b)).all()


@pytest.mark.parametrize("loop", ["closed", "poisson"])
def test_control_one_batch_behind_is_not_correct(loop):
    """The reference, put in the program's place one batch behind, breaks
    read-your-writes: the comparison sees it."""
    c = _case(CELLS[0], loop, 1 << 12, 1 << 14)
    c["traffic"].update(chunk=64, batch_size=64, rate_per_s=3000.0)
    rec = _run(c, seconds=0.5, control=True)
    assert rec["checks"]["wrong_final"]["value"] == 0
    assert rec["checks"]["control_stale"]["value"] > 0


def _unchanged_step(monkeypatch):
    def build(self):
        self._maintain = jax.jit(lambda st, g, d: eng.maintain(self.cfg, st, g, d))
        self._step = lambda st, g, upd: (st, g, eng.zeros_stats())
        self._shed = jax.jit(lambda *a: eng.shed_slot(self.cfg, *a))
    monkeypatch.setattr(eng.DiffIFE, "_build_dispatch", build)


def _half_batch(monkeypatch):
    orig = eng.DiffIFE.apply_updates_batched

    def half(self, updates, batch_size=None):
        updates = list(updates)
        return orig(self, updates[: len(updates) // 2], batch_size=batch_size)
    monkeypatch.setattr(eng.DiffIFE, "apply_updates_batched", half)


def _altered_answer(monkeypatch):
    orig = eng.DiffIFE.answers_row

    def altered(self, slot):
        row = np.array(orig(self, slot), copy=True)
        row[np.flatnonzero(np.isfinite(row))[-1]] += 1.0
        return row
    monkeypatch.setattr(eng.DiffIFE, "answers_row", altered)


@pytest.mark.parametrize("fault", [_unchanged_step, _half_batch, _altered_answer])
@pytest.mark.parametrize("cell,loop", CASES)
def test_fault_in_the_timed_path_is_not_correct(monkeypatch, fault, cell, loop):
    fault(monkeypatch)
    rec = _run(_case(cell, loop))
    assert rec["checks"]["wrong_final"]["value"] > 0
