"""Online replanning policies for the spot-market simulator.

All policies answer the same question at every market event: *given the
fleet as it now stands, which allocation should the next inter-event
interval run under?*  The planning objective is min-cost-under-SLO:
trace (a slice of) the latency-cost frontier for the current fleet and
take the cheapest point whose makespan meets the latency SLO, falling
back to the fastest point when nothing does.

* :class:`StaticPolicy` — plan once at t=0; afterwards only redistribute
  shares stranded on departed platforms (no re-optimisation).
* :class:`ResplitPolicy` — heuristic re-split: the paper's scalarised
  heuristic battery re-run from scratch at every event.
* :class:`WarmMILPPolicy` — warm-started MILP re-solve: a fixed-width
  epsilon-constraint sweep through :func:`repro.core.milp.solve_bnb_sweep`,
  warm-started from the previous allocation and the batched relaxation,
  with dead slots pinned.  Because the fleet is a fixed-width slot array
  every replan reuses ONE compiled stacked-IPM shape.
* :class:`FrontierLookupPolicy` — presolve scenario frontiers for
  anticipated fleet states via :func:`repro.core.pareto.scenario_frontiers`;
  replanning is then a table lookup + projection, no solver in the loop.
* :class:`OraclePolicy` — the clairvoyant reference: the warm-MILP
  machinery at higher effort and a finer budget grid, re-solving every
  inter-event interval with full knowledge of the fleet.  Regret is
  measured against it.
* :class:`ServerBackedPolicy` — Allocation-as-a-Service client: every
  replan is an :class:`~repro.serving.AllocRequest` against a
  continuous-batching :class:`~repro.serving.AllocationServer` (so
  many tenants' replans coalesce into shared stacked-IPM dispatches),
  with a frontier-lookup battery re-presolved in the background when
  the live fleet drifts from the anticipated one.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import numpy as np

from repro.core import heuristics, milp, pareto
from repro.core.problem import AllocationProblem
from repro.market.simulator import PlatformKind, View


def select_cheapest_slo(problem: AllocationProblem, allocs,
                        slo_latency: float) -> np.ndarray:
    """Cheapest allocation meeting the SLO; fastest one when none does."""
    best, best_key = None, None
    fallback, fallback_mk = None, np.inf
    for alloc in allocs:
        if alloc is None:
            continue
        mk, cost = heuristics.evaluate(problem, alloc)
        if mk < fallback_mk:
            fallback, fallback_mk = alloc, mk
        if mk <= slo_latency * (1 + 1e-9):
            key = (cost, mk)
            if best_key is None or key < best_key:
                best, best_key = alloc, key
    if best is not None:
        return best
    if fallback is None:
        raise ValueError("no candidate allocations")
    return fallback


def _mask_to_alive(problem: AllocationProblem, alloc: np.ndarray,
                   dead: np.ndarray) -> np.ndarray:
    """Zero dead-slot rows and renormalise; columns whose whole share was
    stranded on dead slots are refilled latency-proportionally."""
    return milp._project_to_allocation(problem, alloc, ~np.asarray(dead,
                                                                   bool))


class Policy:
    """Replanning interface.  ``replan`` may return the PREVIOUS array
    object unchanged to signal "no replan" (the simulator detects this
    by identity and records the interval as un-replanned)."""
    name = "policy"

    def reset(self, view: View) -> np.ndarray:
        raise NotImplementedError

    def replan(self, view: View, event) -> np.ndarray:
        raise NotImplementedError

    def fused_spec(self):
        """``(policy_kind, n_weights)`` for the fused ``lax.scan`` replay
        (:mod:`repro.market.fused`), or ``None`` when this policy's
        replan has no device port and must run the Python event loop."""
        return None


# ---------------------------------------------------------------------------
# Baselines
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StaticPolicy(Policy):
    """Plan once with the full solver, then never re-optimise.  Shares
    stranded on departed platforms are redistributed (work cannot run on
    a machine that no longer exists) but prices, arrivals and
    degradations are ignored — the no-reaction baseline."""
    n_caps: int = 5
    node_limit: int = 120
    time_limit_s: float = 30.0
    name: str = "static"
    linsolve: str = "xla"
    compact: bool = False
    chunk_iters: Optional[int] = None
    newton_dtype: str = "float64"

    def __post_init__(self):
        self._planner = WarmMILPPolicy(n_caps=self.n_caps,
                                       node_limit=self.node_limit,
                                       time_limit_s=self.time_limit_s,
                                       linsolve=self.linsolve,
                                       compact=self.compact,
                                       chunk_iters=self.chunk_iters,
                                       newton_dtype=self.newton_dtype)

    def reset(self, view: View) -> np.ndarray:
        self._alloc = self._planner.reset(view)
        return self._alloc

    def replan(self, view: View, event) -> np.ndarray:
        stranded = self._alloc[view.dead].sum()
        if stranded <= 1e-12:
            return self._alloc          # identity => "no replan"
        self._alloc = _mask_to_alive(view.problem, self._alloc, view.dead)
        return self._alloc

    def fused_spec(self):
        return ("static", 0)


@dataclasses.dataclass
class ResplitPolicy(Policy):
    """Heuristic re-split at every event: the paper's scalarised sweep
    (plus the latency-proportional split), re-run from scratch on the
    live fleet — reactive but blind to quanta/setup non-linearities."""
    n_weights: int = 9
    name: str = "resplit"

    def _plan(self, view: View) -> np.ndarray:
        p, dead = view.problem, view.dead
        alive = ~dead
        w = np.where(alive, 1.0 / p.single_platform_latency(), 0.0)
        cands: List[np.ndarray] = [heuristics.proportional_split(p, w)]
        for lam in np.linspace(0.0, 1.0, self.n_weights):
            cands.append(_mask_to_alive(p, heuristics.scalarised(
                p, float(lam)), dead))
        return select_cheapest_slo(p, cands, view.slo_latency)

    def reset(self, view: View) -> np.ndarray:
        return self._plan(view)

    def replan(self, view: View, event) -> np.ndarray:
        return self._plan(view)

    def fused_spec(self):
        return ("resplit", self.n_weights)


# ---------------------------------------------------------------------------
# Warm-started MILP replanning (fixed-width stacked solves)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class WarmMILPPolicy(Policy):
    """Warm-started MILP re-solve on every event.

    Each replan traces an ``n_caps``-point budget sweep of the CURRENT
    fleet through :func:`repro.core.milp.solve_bnb_sweep`: one stacked
    relaxation call bounds every budget point, the previous allocation
    (masked to live slots) and the relaxed allocations seed incumbents,
    and dead slots are pinned.  ``batch_width`` is locked to ``n_caps``
    so the relaxation and the node sweep's full rounds share one
    compiled shape; a round that the next ladder width below ``n_caps``
    holds runs at that width (4 at ``n_caps`` 5).  Both widths compile
    on the first replan's sweep, so the episode compiles nothing after it.
    """
    n_caps: int = 5
    node_limit: int = 120
    time_limit_s: float = 30.0
    lp_tol: float = 1e-7
    cap_headroom: float = 1.25
    name: str = "warm_milp"
    # Newton linear-system backend for every stacked solve this policy
    # issues (relaxation grid + lockstep node batches); see
    # :data:`repro.core.lp.LINSOLVES`.
    linsolve: str = "xla"
    # chunked-driver / mixed-precision knobs, threaded into every stacked
    # solve (see :func:`repro.core.lp.solve_lp_stacked`): compact=True
    # retires converged rows mid-call over the fixed width ladder;
    # newton_dtype="float32" runs the f32+refinement Newton path.
    compact: bool = False
    chunk_iters: Optional[int] = None
    newton_dtype: str = "float64"

    def __post_init__(self):
        self._alloc: Optional[np.ndarray] = None

    def _solver_kw(self) -> dict:
        return dict(linsolve=self.linsolve, compact=self.compact,
                    chunk_iters=self.chunk_iters,
                    newton_dtype=self.newton_dtype)

    def _plan(self, view: View) -> np.ndarray:
        p, dead, pin = view.problem, view.dead, view.pin
        c_l, c_u = pareto._cheap_cost_bounds(p, dead)
        caps = np.linspace(c_l, max(c_u, c_l) * self.cap_headroom,
                           self.n_caps)
        _, relax_allocs, lbs = pareto._batched_scenario_relaxation(
            [p], [caps], [dead], **self._solver_kw())
        prev = None
        if self._alloc is not None:
            prev = _mask_to_alive(p, self._alloc, dead)
        warm = [pareto.warm_candidate(p, float(ck),
                                      (prev, relax_allocs[0][j]))
                for j, ck in enumerate(caps)]
        results = milp.solve_bnb_sweep(
            p, caps, warm_allocs=warm,
            lower_bounds0=[float(v) for v in lbs[0]],
            pinned=pin, batch_width=self.n_caps,
            node_limit=self.node_limit, time_limit_s=self.time_limit_s,
            lp_tol=self.lp_tol, **self._solver_kw())
        # the masked previous plan stays in the running: continuity when
        # it is still the cheapest SLO-feasible choice (no churn), and
        # the budget grid can never force a strictly worse plan
        self._alloc = select_cheapest_slo(
            p, [r.alloc for r in results] + [prev], view.slo_latency)
        return self._alloc

    def reset(self, view: View) -> np.ndarray:
        self._alloc = None
        return self._plan(view)

    def replan(self, view: View, event) -> np.ndarray:
        return self._plan(view)


@dataclasses.dataclass
class OraclePolicy(WarmMILPPolicy):
    """PER-INTERVAL clairvoyant: greedy re-solve with full knowledge of
    the fleet, a finer budget grid and a much larger node budget.  Its
    candidate set also contains the whole heuristic battery.

    This is a *diagnostic lower-bound reference*, not the regret
    yardstick: it picks the cheapest SLO-feasible candidate by
    lexicographic (cost, makespan) per interval rather than minimising
    the accrual objective the episode actually bills
    (``cost/makespan`` $/s plus SLA charges), so policies can
    legitimately beat it.  Headline regret is measured against the
    whole-horizon DP (:func:`repro.market.oracle.whole_horizon_oracle`),
    which is non-negative by construction; keep this policy for
    per-interval what-if traces (see docs/market.md)."""
    n_caps: int = 9
    node_limit: int = 500
    time_limit_s: float = 60.0
    lp_tol: float = 1e-9
    name: str = "oracle"

    def _plan(self, view: View) -> np.ndarray:
        milp_pick = super()._plan(view)
        heur_pick = ResplitPolicy()._plan(view)
        self._alloc = select_cheapest_slo(
            view.problem, [milp_pick, heur_pick], view.slo_latency)
        return self._alloc


# ---------------------------------------------------------------------------
# Presolved scenario-frontier lookup
# ---------------------------------------------------------------------------

def anticipated_masks(dead: np.ndarray) -> List[np.ndarray]:
    """The one-event neighbourhood of a fleet state: the current
    dead-mask, the all-alive mask, every one-extra-departure and every
    one-arrival variant (deduplicated).  This is the battery both
    :class:`FrontierLookupPolicy` (as presolved scenarios) and
    :class:`ServerBackedPolicy` (as background presolve requests)
    anticipate from."""
    dead = np.asarray(dead, dtype=bool)
    masks = [np.array(dead), np.zeros_like(dead)]
    for i in np.flatnonzero(~dead):        # one extra departure
        m = np.array(dead)
        m[i] = True
        if (~m).sum() >= 1:
            masks.append(m)
    for i in np.flatnonzero(dead):         # one arrival
        m = np.array(dead)
        m[i] = False
        masks.append(m)
    seen, out = set(), []
    for m in masks:
        key = m.tobytes()
        if key not in seen:
            seen.add(key)
            out.append(m)
    return out

@dataclasses.dataclass
class FrontierLookupPolicy(Policy):
    """Presolve Pareto frontiers for anticipated fleet states, then make
    every replan a lookup.

    At reset the policy builds an *anticipated* fixed-width problem —
    occupied slots keep their platform kind, empty slots are assigned
    catalogue kinds round-robin (the kinds an arrival could bring) — and
    presolves one frontier per anticipated alive-mask through the batched
    :func:`repro.core.pareto.scenario_frontiers` engine.  A replan picks
    the presolved mask nearest (Hamming) to the live fleet, projects its
    frontier points onto the actually-alive slots, and selects the
    cheapest SLO-feasible point.  No solver runs after reset.
    """
    catalog: Sequence[PlatformKind] = ()
    n_points: int = 4
    node_limit: int = 80
    time_limit_s: float = 30.0
    name: str = "frontier_lookup"
    linsolve: str = "xla"
    compact: bool = False
    chunk_iters: Optional[int] = None
    newton_dtype: str = "float64"

    def _anticipated_problem(self, view: View) -> AllocationProblem:
        p = view.problem
        beta = np.array(p.beta)
        gamma = np.array(p.gamma)
        rho = np.array(p.rho)
        pi = np.array(p.pi)
        k = len(self.catalog)
        for s in np.flatnonzero(view.dead):
            kind = self.catalog[int(s) % k]
            beta[s], gamma[s] = kind.beta, kind.gamma
            rho[s], pi[s] = kind.rho, kind.pi
        return AllocationProblem(beta, gamma, p.n, rho, pi,
                                 p.platform_names, p.task_names)

    def _battery(self, view: View):
        from repro.core.scenarios import Scenario, ScenarioSet
        ones = np.ones(view.dead.shape[0])
        scen = [Scenario(f"mask_{i}", ones, ones, ones,
                         np.ones(view.problem.tau), m)
                for i, m in enumerate(anticipated_masks(view.dead))]
        return ScenarioSet(tuple(scen))

    def reset(self, view: View) -> np.ndarray:
        if not self.catalog:
            raise ValueError("FrontierLookupPolicy needs the kind catalog")
        self._battery_set = self._battery(view)
        self._frontiers = pareto.scenario_frontiers(
            self._anticipated_problem(view), self._battery_set,
            n_points=self.n_points, node_limit=self.node_limit,
            time_limit_s=self.time_limit_s, linsolve=self.linsolve,
            compact=self.compact, chunk_iters=self.chunk_iters,
            newton_dtype=self.newton_dtype)
        return self.replan(view, None)

    def replan(self, view: View, event) -> np.ndarray:
        best_name, best_d = None, None
        for s in self._battery_set:
            d = int((s.dead != view.dead).sum())
            if best_d is None or d < best_d:
                best_name, best_d = s.name, d
        tr = self._frontiers[best_name]
        cands = [_mask_to_alive(view.problem, pt.alloc, view.dead)
                 for pt in tr.points]
        return select_cheapest_slo(view.problem, cands, view.slo_latency)


# ---------------------------------------------------------------------------
# Server-backed replanning (Allocation-as-a-Service client)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ServerBackedPolicy(Policy):
    """Route every replan through a continuous-batching
    :class:`~repro.serving.AllocationServer`.

    Each replan submits one :class:`~repro.serving.AllocRequest` for
    the live fleet (an ``n_caps``-point budget sweep with dead slots
    pinned) at ``priority`` and plans from the returned LP frontier:
    the relaxed allocations are projected onto the live slots and the
    cheapest SLO-feasible one wins, with the previous plan kept in the
    running for continuity.  The solver itself — backend, chunked
    driver, precision — is the SERVER's configuration; many policy
    instances (tenants) coalesce into shared stacked dispatches.

    The policy also keeps a :class:`FrontierLookupPolicy`-style battery
    fresh in the BACKGROUND: at reset it submits one presolve request
    per anticipated fleet mask (:func:`anticipated_masks`) at
    ``presolve_priority`` (behind live traffic — presolve rows ride
    along in the spare ladder capacity of later dispatches), and
    whenever the live dead-mask drifts more than ``drift_limit``
    Hamming from every anticipated mask, the battery is re-presolved
    around the NEW fleet state.  Harvested battery frontiers contribute
    fallback candidates to every plan, so a replan still has something
    sensible when its own solve rows fail to converge.
    """
    server: Optional[object] = None        # an AllocationServer
    n_caps: int = 5
    cap_headroom: float = 1.25
    drift_limit: int = 1
    priority: int = 0
    presolve_priority: int = 10
    tenant: str = "server_backed"
    name: str = "server_backed"

    def __post_init__(self):
        if self.server is None:
            raise ValueError("ServerBackedPolicy needs an AllocationServer")
        self._alloc: Optional[np.ndarray] = None
        self._battery: dict = {}           # mask bytes -> (mask, allocs)
        self._pending: list = []           # (mask, future)
        self._anticipated: List[np.ndarray] = []

    def _caps(self, view: View, dead: np.ndarray) -> np.ndarray:
        c_l, c_u = pareto._cheap_cost_bounds(view.problem, dead)
        return np.linspace(c_l, max(c_u, c_l) * self.cap_headroom,
                           self.n_caps)

    def _presolve(self, view: View) -> None:
        """Queue one background presolve request per anticipated mask
        (the live fleet's one-event neighbourhood)."""
        from repro.serving import AllocRequest
        self._anticipated = anticipated_masks(view.dead)
        for i, mask in enumerate(self._anticipated):
            if (~mask).sum() == 0:
                continue
            fut = self.server.submit(AllocRequest(
                f"{self.tenant}/presolve{i}", view.problem,
                self._caps(view, mask), priority=self.presolve_priority,
                dead=mask))
            self._pending.append((mask, fut))

    def _harvest(self) -> None:
        still = []
        for mask, fut in self._pending:
            if fut.done():
                res = fut.result()
                self._battery[mask.tobytes()] = (mask, res.frontier.allocs)
            else:
                still.append((mask, fut))
        self._pending = still

    def _battery_candidates(self, view: View) -> List[np.ndarray]:
        """Projected allocations of the harvested battery entry nearest
        (Hamming) to the live fleet."""
        best = None
        for mask, allocs in self._battery.values():
            d = int((mask != view.dead).sum())
            if best is None or d < best[0]:
                best = (d, allocs)
        if best is None:
            return []
        return [_mask_to_alive(view.problem, a, view.dead)
                for a in best[1]]

    def _drifted(self, view: View) -> bool:
        if not self._anticipated:
            return True
        return min(int((m != view.dead).sum())
                   for m in self._anticipated) > self.drift_limit

    def _plan(self, view: View) -> np.ndarray:
        from repro.serving import AllocRequest
        self._harvest()
        res = self.server.request(AllocRequest(
            self.tenant, view.problem, self._caps(view, view.dead),
            priority=self.priority, dead=view.dead))
        conv = np.asarray(res.frontier.converged)
        cands = [_mask_to_alive(view.problem, a, view.dead)
                 for a, ok in zip(res.frontier.allocs, conv) if ok]
        cands += self._battery_candidates(view)
        if self._alloc is not None:
            cands.append(_mask_to_alive(view.problem, self._alloc,
                                        view.dead))
        if self._drifted(view):
            # the live fleet left the anticipated neighbourhood:
            # re-presolve the battery around the new state, in the
            # background (the results land in later harvests)
            self._presolve(view)
        self._alloc = select_cheapest_slo(view.problem, cands,
                                          view.slo_latency)
        return self._alloc

    def reset(self, view: View) -> np.ndarray:
        self._alloc = None
        self._battery = {}
        self._pending = []
        self._presolve(view)
        return self._plan(view)

    def replan(self, view: View, event) -> np.ndarray:
        return self._plan(view)
