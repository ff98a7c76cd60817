"""Elastic scaling, straggler mitigation and failure handling.

This is where the paper's contribution becomes the framework's
fault-tolerance mechanism: the cluster is modelled as a set of
heterogeneous platforms (pod slices) with fitted (beta, gamma) latency
models; workload shares are an allocation matrix from the MILP.  On a
health event the controller

  * updates the affected platform's beta (degraded throughput — straggler)
    or removes it (failure / elastic scale-down), or appends a platform
    (scale-up),
  * re-solves the allocation under the same cost budget,
  * reports the delta so the serving router / training driver can move
    request shares or re-shard (checkpoint restore with new-mesh
    shardings, `CheckpointManager.restore(..., shardings)`).

Health events can arrive ad hoc (``fail`` / ``report_throughput`` /
``scale_up`` calls) or as a typed spot-market stream: ``apply_event``
consumes :class:`repro.market.events.MarketEvent` records, so the
controller reacts to platform-kind arrivals (scale-up), departures,
spot-price ticks and degradation onsets/recoveries generated by
:func:`repro.market.events.generate_episode`.

Together with the stateless data pipeline (batches are f(seed, step)) and
atomic checkpoints this gives checkpoint/restart fault tolerance with
MILP-optimal post-failure rebalancing instead of naive even re-splits.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core import heuristics, milp, pareto
from repro.core import lp as lpmod
from repro.core.problem import AllocationProblem


@dataclasses.dataclass
class PlatformHealth:
    name: str
    throughput_scale: float = 1.0     # 1.0 healthy; <1 degraded; 0 dead
    alive: bool = True


@dataclasses.dataclass
class ElasticController:
    problem: AllocationProblem
    cost_cap: Optional[float] = None
    backend: str = "bnb"
    straggler_threshold: float = 0.8   # rebalance when throughput < 80%
    # default solver effort for every internally-triggered re-solve
    # (fail / restore / scale_up / apply_event); per-call kwargs win
    solve_kw: dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        self.health: Dict[str, PlatformHealth] = {
            n: PlatformHealth(n) for n in
            (self.problem.platform_names or
             [f"p{i}" for i in range(self.problem.mu)])}
        self._alloc: Optional[np.ndarray] = None
        self._scenario_frontiers: Dict[str, "pareto.Tradeoff"] = {}
        self._base_pi = np.array(self.problem.pi)   # pre-tick prices

    # ------------------------------------------------------------------
    def presolve_scenarios(self, scenario_set=None, n_points: int = 6,
                           **kw) -> Dict[str, "pareto.Tradeoff"]:
        """Precompute a Pareto frontier per anticipated scenario through
        the batched engine (one stacked IPM call for every
        scenario x budget relaxation).  The cached frontiers give instant
        contingency plans and warm starts for post-event re-solves."""
        if scenario_set is None:
            from repro.core import scenarios
            scenario_set = scenarios.standard_suite(self.problem, seed=0)
        self._scenario_frontiers = pareto.scenario_frontiers(
            self.problem, scenario_set, n_points, **kw)
        return self._scenario_frontiers

    def scenario_plan(self, name: str) -> Optional[np.ndarray]:
        """Best presolved allocation for ``name`` within the controller's
        budget (the fastest cached frontier point that fits)."""
        tr = self._scenario_frontiers.get(name)
        if tr is None:
            return None
        best, best_mk = None, np.inf
        for p in tr.points:
            if self.cost_cap is not None and p.cost > self.cost_cap * (1 + 1e-9):
                continue
            if p.makespan < best_mk:
                best, best_mk = p.alloc, p.makespan
        return best

    def _project_live(self, alloc: np.ndarray, live: List[int]
                      ) -> np.ndarray:
        """Restrict a full-pool allocation to live platforms, with shares
        stranded on dead platforms redistributed latency-proportionally."""
        warm = np.array(np.asarray(alloc, dtype=np.float64)[live])
        missing = 1.0 - warm.sum(axis=0)
        if (missing > 1e-9).any():
            lat = (self.problem.beta_n + self.problem.gamma)[live].sum(axis=1)
            w = (1.0 / lat) / (1.0 / lat).sum()
            warm = warm + np.maximum(missing, 0.0)[None, :] * w[:, None]
        return warm

    def _warm_candidate(self, live: List[int]) -> Optional[np.ndarray]:
        """Previous allocation projected onto the live platforms."""
        if self._alloc is None or self._alloc.shape[0] != self.problem.mu:
            return None          # no solve yet, or the pool was resized
        return self._project_live(self._alloc, live)

    # ------------------------------------------------------------------
    def current_problem(self) -> Tuple[AllocationProblem, List[int]]:
        """Problem restricted to live platforms, betas degraded by health."""
        names = list(self.health)
        live = [i for i, n in enumerate(names) if self.health[n].alive]
        if not live:
            raise RuntimeError("no live platforms")
        scale = np.array([1.0 / max(self.health[names[i]].throughput_scale,
                                    1e-6) for i in live])
        p = self.problem
        sub = AllocationProblem(
            p.beta[live] * scale[:, None], p.gamma[live], p.n,
            p.rho[live], p.pi[live],
            tuple(names[i] for i in live), p.task_names)
        return sub, live

    def solve(self, scenario_hint: Optional[str] = None, **kw) -> np.ndarray:
        """Re-solve the allocation for the current health state.

        With the B&B backend the re-solve goes through the batched warm
        path: the previous allocation (projected onto live platforms) and
        any presolved ``scenario_hint`` plan seed the incumbent, and one
        jitted LP relaxation supplies the root lower bound — on a benign
        health event the B&B typically closes at the root with no search.
        """
        kw = {**self.solve_kw, **kw}
        sub, live = self.current_problem()
        if self.backend == "bnb":
            cands = [self._warm_candidate(live)]
            if scenario_hint is not None:
                plan = self.scenario_plan(scenario_hint)
                if plan is not None:
                    cands.append(self._project_live(plan, live))
            warm = pareto.warm_candidate(sub, self.cost_cap, cands)
            # an unsatisfiable budget leaves the relaxation unconverged
            # (the IPM raises nothing): the B&B then starts unbounded
            sol = lpmod.solve_node_lp(sub.node_lp(self.cost_cap))
            lb0 = float(sol.obj) if bool(sol.converged) else None
            res = milp.solve(sub, cost_cap=self.cost_cap, backend="bnb",
                             warm_alloc=warm, lower_bound0=lb0, **kw)
        else:
            res = milp.solve(sub, cost_cap=self.cost_cap,
                             backend=self.backend, **kw)
        if res.alloc is None:
            # budget unsatisfiable after failures -> fall back to fastest
            # feasible (cheapest platform) and surface the violation
            alloc_sub = heuristics.cheapest_single_platform(sub)
        else:
            alloc_sub = res.alloc
        full = np.zeros((self.problem.mu, self.problem.tau))
        for r, i in enumerate(live):
            full[i] = alloc_sub[r]
        self._alloc = full
        return full

    # ------------------------------------------------------------------
    def report_throughput(self, name: str, observed_scale: float
                          ) -> Optional[np.ndarray]:
        """Straggler detection: rebalance if a platform slows past the
        threshold (the paper's 'static allocation performed on a regular
        interval with updated task information' generalised)."""
        h = self.health[name]
        h.throughput_scale = observed_scale
        if observed_scale < self.straggler_threshold:
            return self.solve()
        return None

    def fail(self, name: str) -> np.ndarray:
        self.health[name].alive = False
        return self.solve()

    def restore(self, name: str, throughput_scale: float = 1.0) -> np.ndarray:
        self.health[name].alive = True
        self.health[name].throughput_scale = throughput_scale
        return self.solve()

    def scale_up(self, beta_row: np.ndarray, gamma_row: np.ndarray,
                 rho: float, pi: float, name: str) -> np.ndarray:
        """Elastic scale-up: append a platform and re-solve."""
        p = self.problem
        self.problem = AllocationProblem(
            np.vstack([p.beta, beta_row[None]]),
            np.vstack([p.gamma, gamma_row[None]]),
            p.n, np.append(p.rho, rho), np.append(p.pi, pi),
            tuple(p.platform_names or []) + (name,), p.task_names)
        self.health[name] = PlatformHealth(name)
        self._base_pi = np.append(self._base_pi, pi)
        return self.solve()

    def reprice(self, name: str, price_scale: float) -> np.ndarray:
        """Spot-price tick: rescale a platform's per-quantum price
        (relative to the ORIGINAL catalogue price) and re-solve — a
        repricing shifts the cost-optimal split even when every machine
        is healthy."""
        names = list(self.health)
        i = names.index(name)
        p = self.problem
        pi = np.array(p.pi)
        pi[i] = self._base_pi[i] * float(price_scale)
        self.problem = AllocationProblem(p.beta, p.gamma, p.n, p.rho, pi,
                                         p.platform_names, p.task_names)
        return self.solve()

    # ------------------------------------------------------------------
    def apply_event(self, event, catalog=None) -> Optional[np.ndarray]:
        """Consume one typed spot-market event
        (:class:`repro.market.events.MarketEvent`).

        Arrivals need ``catalog`` (a sequence of
        :class:`repro.market.simulator.PlatformKind`) to resolve the
        arriving kind's model rows — the controller gains a platform and
        re-solves (scale-up on arrival).  Returns the new allocation, or
        None when the event needed no rebalance (mild degradation).
        """
        from repro.market import events as mev
        if event.kind == mev.ARRIVAL:
            if catalog is None:
                raise ValueError("arrival events need the kind catalog")
            kind = catalog[int(event.get("kind_index"))]
            return self.scale_up(kind.beta, kind.gamma, kind.rho, kind.pi,
                                 event.platform)
        if event.kind == mev.DEPARTURE:
            return self.fail(event.platform)
        if event.kind == mev.PRICE_TICK:
            return self.reprice(event.platform,
                                float(event.get("price_scale")))
        if event.kind == mev.DEGRADE:
            return self.report_throughput(
                event.platform, 1.0 / float(event.get("beta_scale")))
        if event.kind == mev.RECOVER:
            return self.restore(event.platform)
        raise ValueError(f"unknown event kind {event.kind!r}")

    @property
    def allocation(self) -> Optional[np.ndarray]:
        return self._alloc
