"""Heuristic partitioners (paper §II.B, §III.C and Braun et al. baselines).

All heuristics return a dense allocation matrix A of shape (mu, tau) with
columns summing to 1.  They are intentionally "common sense": they reason
about absolute latency/cost only and ignore the non-linearities (setup
constant gamma, billing quantum rho) — exactly the blind spot the paper's
MILP exploits.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from repro.core.problem import AllocationProblem


def evaluate(problem: AllocationProblem, alloc: np.ndarray):
    """(makespan_s, cost_$) of an allocation under the true models."""
    alloc = np.asarray(alloc, dtype=np.float64)
    setup = (alloc > 1e-12).astype(np.float64)
    g_l = (problem.beta_n * alloc + problem.gamma * setup).sum(axis=1)
    makespan = float(g_l.max())
    cost = float((np.ceil(g_l / problem.rho - 1e-12) * problem.pi).sum())
    return makespan, cost


def cheapest_single_platform(problem: AllocationProblem,
                             allowed: Optional[np.ndarray] = None
                             ) -> np.ndarray:
    """Paper step 2: the lower cost bound C_L — everything on the platform
    that finishes the whole workload cheapest.  ``allowed`` (mu,) bool
    restricts the choice (dead platforms / pinned fleet slots)."""
    cost = problem.single_platform_cost()
    if allowed is not None:
        cost = np.where(np.asarray(allowed, bool), cost, np.inf)
    i = int(np.argmin(cost))
    alloc = np.zeros((problem.mu, problem.tau))
    alloc[i, :] = 1.0
    return alloc


def proportional_split(problem: AllocationProblem,
                       weights: Optional[np.ndarray] = None) -> np.ndarray:
    """Paper step 1 heuristic: divide work inversely proportional to each
    platform's single-platform makespan (or explicit weights)."""
    if weights is None:
        weights = 1.0 / problem.single_platform_latency()
    weights = np.maximum(np.asarray(weights, dtype=np.float64), 0.0)
    if weights.sum() <= 0:
        raise ValueError("all-zero weights")
    share = weights / weights.sum()
    return np.tile(share[:, None], (1, problem.tau))


def scalarised(problem: AllocationProblem, cost_weight: float) -> np.ndarray:
    """Paper step 3 heuristic: weight platforms by a linear combination of
    normalised latency and cost; as cost_weight -> 1 the split collapses
    onto the cheap platforms (C_U -> C_L)."""
    lat = problem.single_platform_latency()
    cost = problem.single_platform_cost()
    lat_n = lat / lat.max()
    cost_n = cost / cost.max()
    score = (1.0 - cost_weight) * lat_n + cost_weight * cost_n
    weights = 1.0 / np.maximum(score, 1e-12)
    if cost_weight >= 1.0:
        return cheapest_single_platform(problem)
    # sharpen: platforms with score > x * best get dropped as the cost
    # weighting rises (the paper's heuristic "moves" along the frontier)
    cutoff = np.quantile(score, max(0.05, 1.0 - cost_weight))
    weights = np.where(score <= cutoff, weights, 0.0)
    if weights.sum() <= 0:
        return cheapest_single_platform(problem)
    return proportional_split(problem, weights)


def min_min(problem: AllocationProblem) -> np.ndarray:
    """Braun et al. Min-min list scheduler with WHOLE task assignment
    (binary A) — the classic heuristic baseline for atomic tasks.

    Each step places the task with the least earliest completion time
    (ties: lowest task, then lowest platform).  A task's earliest
    completion moves only when its best platform's ready time does, so
    a step recomputes just those tasks: O(tau) numpy steps in all."""
    mu, tau = problem.mu, problem.tau
    bn, gm = problem.beta_n, problem.gamma
    ready = np.zeros(mu)                       # platform busy-until
    alloc = np.zeros((mu, tau))
    ect = (ready[:, None] + bn) + gm
    best_i = ect.argmin(axis=0)                # each task's best platform
    best = ect[best_i, np.arange(tau)]         # and its completion time
    for _ in range(tau):
        j = int(np.argmin(best))
        i = int(best_i[j])
        alloc[i, j] = 1.0
        best[j] = np.inf
        ready[i] += bn[i, j] + gm[i, j]
        stale = np.flatnonzero((best_i == i) & np.isfinite(best))
        if stale.size:
            col = (ready[:, None] + bn[:, stale]) + gm[:, stale]
            best_i[stale] = col.argmin(axis=0)
            best[stale] = col[best_i[stale], np.arange(stale.size)]
    return alloc


def repair_to_budget(problem: AllocationProblem, alloc: np.ndarray,
                     cost_cap: float, max_rounds: Optional[int] = None,
                     allowed: Optional[np.ndarray] = None
                     ) -> Optional[np.ndarray]:
    """Greedy repair: deactivate the platform with the worst marginal
    cost-per-work until the billed cost fits the budget.  Returns None if
    even the cheapest single platform exceeds the budget.  ``allowed``
    (mu,) restricts the single-platform fallback to a subset of
    platforms (the greedy loop itself never adds mass to an inactive
    row, so an ``alloc`` clean of disallowed rows stays clean)."""
    alloc = np.array(alloc, dtype=np.float64)
    max_rounds = max_rounds or problem.mu
    for _ in range(max_rounds):
        _, cost = evaluate(problem, alloc)
        if cost <= cost_cap * (1 + 1e-9):
            return alloc
        active = alloc.sum(axis=1) > 1e-12
        if active.sum() <= 1:
            break
        g_l = (problem.beta_n * alloc
               + problem.gamma * (alloc > 1e-12)).sum(axis=1)
        billed = np.ceil(g_l / problem.rho) * problem.pi
        work = alloc.sum(axis=1)
        waste = np.where(active, billed / np.maximum(work, 1e-9), -np.inf)
        drop = int(np.argmax(waste))
        # move the dropped platform's share onto remaining active platforms
        keep = active.copy()
        keep[drop] = False
        w = np.where(keep, 1.0 / problem.single_platform_latency(), 0.0)
        redistribute = alloc[drop][None, :] * (w / w.sum())[:, None]
        alloc = alloc + redistribute
        alloc[drop] = 0.0
    cheap = cheapest_single_platform(problem, allowed)
    _, cost = evaluate(problem, cheap)
    return cheap if cost <= cost_cap * (1 + 1e-9) else None


def best_heuristic_for_budget(problem: AllocationProblem, cost_cap: float,
                              n_weights: int = 17) -> Optional[np.ndarray]:
    """The heuristic competitor used in the paper's Table IV: sweep the
    scalarisation weight, keep the lowest-makespan allocation within
    budget (repairing if needed)."""
    best, best_mk = None, np.inf
    for lam in np.linspace(0.0, 1.0, n_weights):
        cand = scalarised(problem, float(lam))
        mk, cost = evaluate(problem, cand)
        if cost > cost_cap * (1 + 1e-9):
            cand = repair_to_budget(problem, cand, cost_cap)
            if cand is None:
                continue
            mk, cost = evaluate(problem, cand)
        if cost <= cost_cap * (1 + 1e-9) and mk < best_mk:
            best, best_mk = cand, mk
    return best
