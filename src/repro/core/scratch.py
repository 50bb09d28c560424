"""SCRATCH baseline (§6.1.3): re-execute the static IFE after every batch.

Identical step function to the engine's JOD path — the same "incremental"
fixpoint loop the original DD paper calls the static algorithm — but no
difference sets are kept (zero maintenance memory, maximal recompute cost).

:class:`ScratchEngine` is the session-protocol form (`core/session.py`):
queries register/deregister as :class:`~repro.core.plan.QueryPlan` rows of
a host-side init matrix; every update batch re-runs the static IFE for the
whole matrix.  :class:`Scratch` remains the fixed-batch legacy wrapper.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import plan as qp
from repro.core.engine import (
    ITER_TRACE,
    EngineConfig,
    GraphArrays,
    MaintainStats,
    ife_step,
    zeros_stats,
)
from repro.core.graph import DynamicGraph

Array = jnp.ndarray


@partial(jax.jit, static_argnums=0)
def scratch_run(
    cfg: EngineConfig, g: GraphArrays, init: Array
) -> tuple[Array, MaintainStats]:
    """Run IFE to fixpoint (or max_iters) from the initial states.

    Stats come back in the dense engine's :class:`MaintainStats` schema so
    telemetry / governor / metrics observe one uniform shape across engines;
    fields SCRATCH has no analog for (change points, drops, repairs) are
    structurally zero.  ``scheduled`` is V × iters per query — every vertex
    reruns every iteration, the baseline's defining cost.
    """

    def body(carry):
        i, cur, _ = carry
        new = ife_step(cfg, cur, g)
        changed = (new != cur).any()
        return (i + 1, new, changed)

    def cond(carry):
        i, _, changed = carry
        return (i <= jnp.int32(cfg.max_iters)) & changed

    i, final, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(1), init, jnp.bool_(True))
    )
    iters = i - 1
    q, v = init.shape
    per_iter = jnp.int32(q * v)
    # per-iteration schedule series: every iteration reruns the full matrix;
    # iterations beyond the trace depth fold into the last bin (as dense)
    bins = jnp.arange(ITER_TRACE, dtype=jnp.int32)
    sched_sizes = jnp.where(bins < jnp.minimum(iters, ITER_TRACE), per_iter, 0)
    overflow = jnp.maximum(iters - ITER_TRACE, 0) * per_iter
    sched_sizes = sched_sizes.at[ITER_TRACE - 1].add(overflow)
    stats = zeros_stats()._replace(
        iters_run=iters,
        scheduled=iters * per_iter,
        # every row reruns every iteration, reading every live in-edge
        active_edges=(iters * q).astype(jnp.float32)
        * g.valid.sum(dtype=jnp.float32),
        sched_sizes=sched_sizes,
    )
    return final, stats


class Scratch:
    """From-scratch continuous query processor (the paper's SCRATCH)."""

    def __init__(self, cfg: EngineConfig, graph: DynamicGraph, init) -> None:
        self.cfg = cfg
        self.graph = graph
        self.init = jnp.asarray(init, jnp.float32)
        self.g = GraphArrays.from_snapshot(graph.snapshot(), backend=cfg.backend)
        self._answers, self.last_stats = scratch_run(cfg, self.g, self.init)

    def apply_updates(self, updates) -> MaintainStats:
        self.graph.apply_batch(updates)
        self.g = GraphArrays.from_snapshot(self.graph.snapshot(), backend=self.cfg.backend)
        self._answers, self.last_stats = scratch_run(self.cfg, self.g, self.init)
        return self.last_stats

    def answers(self) -> np.ndarray:
        return np.asarray(self._answers)

    def nbytes(self) -> int:
        return 0  # no differences maintained


def scratch_like(engine_cfg: EngineConfig, graph: DynamicGraph, init) -> Scratch:
    """Scratch twin of a Diff-IFE engine (same semiring/query batch)."""
    return Scratch(engine_cfg, graph, init)


class ScratchEngine:
    """From-scratch CQP with a runtime query lifecycle (session protocol).

    Registered plans occupy rows of a host-side init matrix; re-execution
    covers all live rows in one jitted run (a row-count change retraces —
    SCRATCH is the baseline, not the throughput path).  ``nbytes`` is 0 by
    construction: no differences are ever maintained.
    """

    def __init__(self, cfg: EngineConfig, graph: DynamicGraph) -> None:
        self.cfg = cfg  # num_queries tracks the slot count
        self.graph = graph
        self.plans: dict[int, qp.QueryPlan] = {}
        self._rows: dict[int, np.ndarray] = {}
        self._free: list[int] = []
        self._num_slots = 0
        self.g = GraphArrays.from_snapshot(graph.snapshot(), backend=cfg.backend)
        self._answers = np.zeros((0, cfg.num_vertices), np.float32)
        self.last_stats: MaintainStats | None = None

    # ---------------------------------------------------------------- slots
    def register_plan(self, plan: qp.QueryPlan) -> int:
        return self.register_plans([plan])[0]

    def register_plans(self, plans: list[qp.QueryPlan]) -> list[int]:
        """Batch registration: claim all slots first, re-execute ONCE (a
        per-plan rerun would retrace for every new row count)."""
        slots = []
        for plan in plans:
            slot = self._free.pop() if self._free else self._num_slots
            self._num_slots = max(self._num_slots, slot + 1)
            self.plans[slot] = plan
            self._rows[slot] = plan.build_init(self.cfg.num_vertices)
            slots.append(slot)
        self._rerun()
        return slots

    def deregister_plan(self, slot: int) -> int:
        if slot not in self.plans:
            raise ValueError(f"slot {slot} is not registered")
        del self.plans[slot], self._rows[slot]
        self._free.append(slot)
        self._free.sort(reverse=True)
        # keep answers() slot-aligned with the other engines: a freed slot
        # reads as the identity row, without re-running the computation
        if slot < self._answers.shape[0]:
            self._answers[slot] = self.cfg.semiring.identity
        if not self.plans:
            self._answers = np.zeros((0, self.cfg.num_vertices), np.float32)
        return 0  # SCRATCH holds no differences

    def active_slots(self) -> list[int]:
        return sorted(self.plans)

    # ----------------------------------------------------- governor surface
    def nbytes_per_query(self) -> dict[int, int]:
        return {s: 0 for s in sorted(self.plans)}  # SCRATCH holds no diffs

    def nbytes_per_operator(self) -> dict[int, dict[str, int]]:
        """Operator-addressed view: zero by construction for every store."""
        return {s: {"iterate": 0} for s in sorted(self.plans)}

    def recompute_cost_per_query(self) -> dict[int, int]:
        """Every slot pays the full re-execution; apportion the cumulative
        scheduled count evenly so the governor's signals stay comparable."""
        n = max(len(self.plans), 1)
        total = 0 if self.last_stats is None else int(self.last_stats.scheduled)
        return {s: total // n for s in sorted(self.plans)}

    def recompute_cost_per_operator(self) -> dict[int, dict[str, int]]:
        per = self.recompute_cost_per_query()
        return {s: {"iterate": c} for s, c in per.items()}

    def set_drop_params(self, slot: int, cfg, op_id: str = "iterate") -> int:
        """SCRATCH is already the zero-memory endpoint of the ladder."""
        if slot not in self.plans:
            raise ValueError(f"slot {slot} is not registered")
        return 0

    # ------------------------------------------------------------ execution
    def _init_matrix(self) -> np.ndarray:
        """[num_slots, V]; retired slots re-run as identity rows (their
        lanes are dead weight until the slot is reused — SCRATCH is the
        recompute-everything baseline by definition)."""
        ident = self.cfg.semiring.identity
        init = np.full(
            (self._num_slots, self.cfg.num_vertices), ident, np.float32
        )
        for slot, row in self._rows.items():
            init[slot] = row
        return init

    def _rerun(self) -> None:
        if not self.plans:
            self._answers = np.zeros((0, self.cfg.num_vertices), np.float32)
            return
        cfg = dataclasses.replace(self.cfg, num_queries=self._num_slots)
        ans, self.last_stats = scratch_run(cfg, self.g, jnp.asarray(self._init_matrix()))
        self._answers = np.array(ans)  # writable copy: deregister blanks rows

    def apply_updates(self, updates):
        self.graph.apply_batch(updates)
        self.g = GraphArrays.from_snapshot(
            self.graph.snapshot(), backend=self.cfg.backend
        )
        self._rerun()
        return self.last_stats

    def apply_updates_batched(self, updates, batch_size: int | None = None):
        del batch_size
        return self.apply_updates(list(updates))

    # ------------------------------------------------------------------ api
    def answers_row(self, slot: int) -> np.ndarray:
        if slot not in self.plans:
            raise ValueError(f"slot {slot} is not registered")
        return self._answers[slot].copy()

    def answers(self) -> np.ndarray:
        return self._answers.copy()

    def nbytes(self) -> int:
        return 0  # no differences maintained

    # ------------------------------------------------------------ durability
    def export_state(self) -> tuple[dict[str, np.ndarray], dict]:
        """SCRATCH holds no differences: the checkpoint is just the plan
        rows plus the work counters the governor reads.  Answers are
        re-derived from the restored graph at import time."""
        meta = {
            "num_slots": int(self._num_slots),
            "free_slots": [int(s) for s in self._free],
            "plans": {str(s): p.to_json() for s, p in self.plans.items()},
            "last_iters": (
                None if self.last_stats is None else int(self.last_stats.iters_run)
            ),
            "last_scheduled": (
                None if self.last_stats is None else int(self.last_stats.scheduled)
            ),
        }
        return {}, meta

    def import_state(self, arrays: dict, meta: dict) -> None:
        del arrays
        self.plans = {
            int(s): qp.QueryPlan.from_json(p) for s, p in meta["plans"].items()
        }
        self._num_slots = int(meta["num_slots"])
        self._free = [int(s) for s in meta["free_slots"]]
        self._rows = {
            s: p.build_init(self.cfg.num_vertices) for s, p in self.plans.items()
        }
        self._rerun()
        if meta["last_iters"] is not None:
            # the pre-crash run's counters, not the import rerun's, so the
            # governor's recompute signal continues where it left off
            self.last_stats = zeros_stats()._replace(
                iters_run=jnp.int32(meta["last_iters"]),
                scheduled=jnp.int32(meta["last_scheduled"]),
            )
