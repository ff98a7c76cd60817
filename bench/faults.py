"""Faults planted in the program underneath a run, for the check that
each turns ``correct`` false: an answer altered where it is produced,
half of a batch answered with copies of the other half, and a solve or
step that leaves its state where it started.

``FAULTS`` maps (driver, fault) to a function that plants the fault
through an object with ``setattr(obj, name, value)``: pytest's
``monkeypatch`` in the tests, :class:`Patch` in ``bench/control.py``,
which reads the faults at a cell's own size on the chip.
"""
from __future__ import annotations

import dataclasses

import numpy as np


class Patch:
    """``setattr`` that remembers what it replaced; :meth:`undo` puts it
    back."""

    def __init__(self):
        self._saved = []

    def setattr(self, obj, name: str, value) -> None:
        self._saved.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def undo(self) -> None:
        while self._saved:
            obj, name, value = self._saved.pop()
            setattr(obj, name, value)


def _rows_copied_from_first_half(sol, k: int):
    h = (k + 1) // 2
    idx = np.r_[np.arange(h), np.arange(k - h)]
    return type(sol)(*(np.asarray(f)[idx] for f in sol))


def served_answer_altered(mp):
    from repro.core import pareto
    orig = pareto.tenant_frontiers

    def bad(problems, caps_list, sol):
        out = orig(problems, caps_list, sol)
        out[0].makespans[0] *= 1.001
        return out
    mp.setattr(pareto, "tenant_frontiers", bad)


def served_half_batch(mp):
    from repro.core import lp
    orig = lp.solve_node_lps_ladder

    def bad(nodes, **kw):
        nodes = list(nodes)
        return _rows_copied_from_first_half(orig(nodes, **kw), len(nodes))
    mp.setattr(lp, "solve_node_lps_ladder", bad)


def served_state_unchanged(mp):
    from repro.serving import server
    orig = server.AllocationServer.__init__

    def init(self, **kw):
        orig(self, **kw, max_iters=0)
    mp.setattr(server.AllocationServer, "__init__", init)


def milp_answer_altered(mp):
    from repro.core import pareto
    orig = pareto.milp_tradeoff_batched

    def bad(*a, **kw):
        out = orig(*a, **kw)
        out.points[0].makespan *= 1 - 1e-3
        return out
    mp.setattr(pareto, "milp_tradeoff_batched", bad)


def milp_half_batch(mp):
    from repro.core import lp
    orig = lp.solve_node_lps_stacked

    def bad(nodes, **kw):
        nodes = list(nodes)
        k = len(nodes)
        if kw.get("row_active") is not None:
            k = int(np.asarray(kw["row_active"]).sum()) or k
        sol = orig(nodes, **kw)
        n = len(nodes)
        h = (k + 1) // 2
        idx = np.r_[np.arange(h), np.arange(k - h), np.arange(k, n)]
        return type(sol)(*(np.asarray(f)[idx] for f in sol))
    mp.setattr(lp, "solve_node_lps_stacked", bad)


def milp_state_unchanged(mp):
    # stacked solves that never leave their starting point yet report
    # convergence (an unconverged node would go to the host's HiGHS)
    from repro.core import lp
    orig = lp.solve_lp_stacked

    def bad(*a, **kw):
        sol = orig(*a, **dict(kw, max_iters=0))
        zero = np.zeros(np.shape(sol.gap))
        return sol._replace(primal_res=zero, dual_res=zero, gap=zero)
    mp.setattr(lp, "solve_lp_stacked", bad)


def _fused_totals_patch(mp, change):
    from repro.market import fused
    orig = fused.run_episodes_vmapped

    def bad(*a, **kw):
        return change(list(orig(*a, **kw)))
    mp.setattr(fused, "run_episodes_vmapped", bad)


def regret_answer_altered(mp):
    _fused_totals_patch(mp, lambda out: [
        dataclasses.replace(t, accrued_cost=t.accrued_cost * (1 + 1e-6))
        for t in out])


def regret_half_batch(mp):
    def change(out):
        h = (len(out) + 1) // 2
        return out[:h] + out[:len(out) - h]
    _fused_totals_patch(mp, change)


def regret_state_unchanged(mp):
    from repro.market import fused

    def replan_fn(policy_kind, n_weights):
        def replan(cat, occ, kind, bsc, psc, csc, alloc, slo):
            import jax.numpy as jnp
            return alloc, jnp.asarray(True)
        return replan
    mp.setattr(fused, "_FUSED_REPLAYS", {})
    mp.setattr(fused, "_replan_fn", replan_fn)


FAULTS = {
    ("served", "answer_altered"): served_answer_altered,
    ("served", "half_batch"): served_half_batch,
    ("served", "state_unchanged"): served_state_unchanged,
    ("milp", "answer_altered"): milp_answer_altered,
    ("milp", "half_batch"): milp_half_batch,
    ("milp", "state_unchanged"): milp_state_unchanged,
    ("regret", "answer_altered"): regret_answer_altered,
    ("regret", "half_batch"): regret_half_batch,
    ("regret", "state_unchanged"): regret_state_unchanged,
}
