"""Pareto trade-off generation (paper §III.C, epsilon-constraint method).

Procedure (verbatim from the paper):
  1. upper cost bound C_U : minimise latency with NO cost constraint;
  2. lower cost bound C_L : cheapest single platform;
  3. iterate C_k evenly between C_L and C_U (Kirlik & Sayin style
     epsilon-constraint), one MILP per C_k; the heuristic competitor
     sweeps its scalarisation weight instead.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import numpy as np

from repro import obs
from repro.core import heuristics, milp
from repro.core.problem import AllocationProblem


@dataclasses.dataclass
class TradeoffPoint:
    cost_cap: Optional[float]
    makespan: float
    cost: float
    alloc: np.ndarray
    meta: dict


@dataclasses.dataclass
class Tradeoff:
    points: List[TradeoffPoint]
    c_lower: float
    c_upper: float
    method: str

    def as_arrays(self):
        pts = sorted(self.points, key=lambda p: p.cost)
        return (np.array([p.cost for p in pts]),
                np.array([p.makespan for p in pts]))


def pareto_filter(costs: np.ndarray, latencies: np.ndarray) -> np.ndarray:
    """Boolean mask of non-dominated (cost, latency) points (min-min)."""
    costs = np.asarray(costs, float)
    latencies = np.asarray(latencies, float)
    keep = np.ones(len(costs), bool)
    for i in range(len(costs)):
        dominated = ((costs <= costs[i]) & (latencies <= latencies[i])
                     & ((costs < costs[i]) | (latencies < latencies[i])))
        if dominated.any():
            keep[i] = False
    return keep


def hypervolume(costs: np.ndarray, latencies: np.ndarray,
                ref_cost: float, ref_lat: float) -> float:
    """2-D hypervolume dominated w.r.t. the reference point (bigger=better)."""
    mask = pareto_filter(costs, latencies)
    pts = sorted(zip(np.asarray(costs)[mask], np.asarray(latencies)[mask]))
    hv, prev_lat = 0.0, ref_lat
    for c, l in pts:
        if c >= ref_cost or l >= prev_lat:
            continue
        hv += (ref_cost - c) * (prev_lat - l)
        prev_lat = l
    return hv


def cost_bounds(problem: AllocationProblem, backend: str = "bnb", **kw):
    """(C_L, C_U, unconstrained-result).  C_U from the unconstrained MILP.

    Note a divergence from the paper's step 2: the cheapest SINGLE
    platform is not always the cheapest allocation — billing-quantum
    packing can make a split both faster and cheaper — so C_L is clamped
    by the unconstrained optimum's realised cost.
    """
    c_l = float(problem.single_platform_cost().min())
    res = milp.solve(problem, cost_cap=None, backend=backend, **kw)
    c_u = float(res.cost)
    return min(c_l, c_u), c_u, res


def cost_bounds_batched(problem: AllocationProblem, **kw):
    """:func:`cost_bounds` with the unconstrained solve routed through the
    batched (width-1 lockstep) B&B — the exploration order matches the
    serial solver exactly, but every node LP runs as one fully jitted
    call instead of the eager serial path.  A caller's ``batch_width``
    (a sweep tuning knob) is ignored here: the anchor always runs at
    width 1 so its result is engine-independent."""
    kw = dict(kw)
    kw.pop("batch_width", None)
    c_l = float(problem.single_platform_cost().min())
    res = milp.solve_bnb_sweep(problem, [None], batch_width=1, **kw)[0]
    c_u = float(res.cost)
    return min(c_l, c_u), c_u, res


def milp_tradeoff(problem: AllocationProblem, n_points: int = 8,
                  backend: str = "bnb", **kw) -> Tradeoff:
    c_l, c_u, top = cost_bounds(problem, backend=backend, **kw)
    points = []
    caps = np.linspace(c_l, max(c_u, c_l), n_points)
    for ck in caps:
        res = milp.solve(problem, cost_cap=float(ck), backend=backend, **kw)
        if res.alloc is None:
            continue
        points.append(TradeoffPoint(float(ck), res.makespan, res.cost,
                                    res.alloc,
                                    dict(status=res.status, nodes=res.nodes,
                                         lb=res.lower_bound)))
    # the unconstrained optimum anchors the fast end
    points.append(TradeoffPoint(None, top.makespan, top.cost, top.alloc,
                                dict(status=top.status, nodes=top.nodes,
                                     lb=top.lower_bound)))
    return Tradeoff(points, c_l, c_u, f"milp-{backend}")


def relaxation_frontier(problem: AllocationProblem, caps: np.ndarray,
                        *, return_solutions: bool = False,
                        linsolve: str = "xla", compact: bool = False,
                        chunk_iters: Optional[int] = None,
                        newton_dtype: str = "float64", mesh=None,
                        row_spec=None):
    """Instant LOWER-BOUND frontier: the LP relaxation of Eq. 4 solved for
    every cost cap in ONE vmapped interior-point call (the epsilon grid
    shares the constraint matrix; only the budget rhs varies).

    Returns (caps, lb_makespans) — every true (MILP/heuristic) frontier
    point lies on or above this curve — used as the optimality reference
    in plots and as B&B seed bounds.  With ``return_solutions`` the full
    batched :class:`~repro.core.lp.LPSolution` is appended so callers can
    warm-start from the relaxed allocations.
    """
    from repro.core import lp as lpmod
    caps = np.asarray(caps, dtype=np.float64)
    node = problem.node_lp(cost_cap=float(caps[0]))
    # cost row is the LAST inequality row by construction; only the rhs
    # is batched, so the node's rows are copied once
    h_batch = np.tile(np.asarray(node.h), (len(caps), 1))
    h_batch[:, -1] = caps
    sols = lpmod.solve_lp_stacked(node.c, node.rows, None, None,
                                  h_batch, node.lb, node.ub,
                                  linsolve=linsolve, compact=compact,
                                  chunk_iters=chunk_iters,
                                  newton_dtype=newton_dtype, mesh=mesh,
                                  row_spec=row_spec)
    if return_solutions:
        return caps, np.asarray(sols.obj), sols
    return caps, np.asarray(sols.obj)


# ---------------------------------------------------------------------------
# Batched frontier engine (warm-started epsilon-constraint sweep)
# ---------------------------------------------------------------------------

def warm_candidate(problem: AllocationProblem, cost_cap: Optional[float],
                   candidates) -> Optional[np.ndarray]:
    """Best feasible (possibly repaired) incumbent among ``candidates``
    for a B&B warm start; ``cost_cap=None`` means unconstrained.  Public
    because runtime callers (e.g. the elastic controller) use it to seed
    re-solves."""
    best, best_mk = None, np.inf
    for cand in candidates:
        if cand is None:
            continue
        cand = milp._project_to_allocation(problem, cand)
        a, mk, _ = milp._round_incumbent(problem, cand, cost_cap)
        if a is not None and mk < best_mk:
            best, best_mk = a, mk
    return best


_warm_candidate = warm_candidate          # internal alias


def _warm_sweep(problem: AllocationProblem, caps: np.ndarray,
                relax_lbs: np.ndarray, relax_allocs, top, **kw
                ) -> List[TradeoffPoint]:
    """Solve a whole epsilon grid through the lockstep batched B&B
    (:func:`repro.core.milp.solve_bnb_sweep`), seeding every budget point
    from its batched-relaxation entry and the unconstrained optimum."""
    warm = [_warm_candidate(problem, float(ck),
                            (top.alloc, relax_allocs[j]))
            for j, ck in enumerate(caps)]
    results = milp.solve_bnb_sweep(
        problem, caps, warm_allocs=warm,
        lower_bounds0=[float(v) for v in relax_lbs], **kw)
    return [TradeoffPoint(float(ck), r.makespan, r.cost, r.alloc,
                          dict(status=r.status, nodes=r.nodes,
                               lb=r.lower_bound))
            for ck, r in zip(caps, results) if r.alloc is not None]


def milp_tradeoff_batched(problem: AllocationProblem, n_points: int = 8,
                          backend: str = "bnb", **kw) -> Tradeoff:
    """Batched counterpart of :func:`milp_tradeoff` (B&B backend only).

    All epsilon-constraint budget points share one jitted, vmapped
    interior-point relaxation solve; each point's B&B then warm-starts
    from the batched relaxation (lower bound + rounded allocation) and
    from its sweep neighbour's incumbent, so most points close at the
    root with zero nodes.  Results match :func:`milp_tradeoff` within
    solver tolerance.  A ``linsolve=`` kwarg routes every stacked Newton
    solve — relaxation grid and lockstep node batches alike — through the
    chosen backend (:data:`repro.core.lp.LINSOLVES`); ``compact=`` /
    ``chunk_iters=`` / ``newton_dtype=`` likewise steer every stacked
    solve onto the chunked mid-call-compaction driver and/or the
    mixed-precision Newton path (see :func:`repro.core.lp.solve_lp_stacked`).
    ``mesh=`` / ``row_spec=`` shard the big relaxation megabatch over a
    device mesh (the narrow lockstep node batches inside B&B stay
    unsharded — see ``_bnb_kw``).
    """
    if backend != "bnb":
        for k in ("linsolve", "early_exit", "compact", "chunk_iters",
                  "newton_dtype", "mesh", "row_spec"):
            kw.pop(k, None)
        return milp_tradeoff(problem, n_points, backend=backend, **kw)
    with obs.span("pareto.sweep", n_points=n_points):
        with obs.span("pareto.anchor"):
            c_l, c_u, top = cost_bounds_batched(problem, **_bnb_kw(kw))
        caps = np.linspace(c_l, max(c_u, c_l), n_points)
        with obs.span("pareto.relaxation"):
            _, lbs, sols = relaxation_frontier(problem, caps,
                                               return_solutions=True,
                                               **_stacked_solve_kw(kw))
            lbs = _trusted_bounds(lbs, sols.converged)
            xs = np.asarray(sols.x)
            relax_allocs = [problem.split_node_x(xs[k])[0]
                            for k in range(len(caps))]
        with obs.span("pareto.bnb"):
            points = _warm_sweep(problem, caps, lbs, relax_allocs, top,
                                 **_bnb_kw(kw))
    points.append(TradeoffPoint(None, top.makespan, top.cost, top.alloc,
                                dict(status=top.status, nodes=top.nodes,
                                     lb=top.lower_bound)))
    return Tradeoff(points, c_l, c_u, "milp-bnb-batched")


# ---------------------------------------------------------------------------
# Merged-batch frontier slicing (the serving result path)
# ---------------------------------------------------------------------------

def frontier_nodes(problem: AllocationProblem, caps,
                   dead: Optional[np.ndarray] = None) -> list:
    """One relaxation :class:`~repro.core.problem.NodeLP` per budget cap
    — the LP rows an allocation request expands to before batching.

    All nodes share the constraint matrix; only the budget rhs (the
    LAST inequality row by construction) varies.  Dead platforms are
    pinned to zero allocation via the node's variable bounds, exactly
    as the scenario and market paths do.
    """
    from repro.core.scenarios import dead_pin_mask
    caps = np.asarray(caps, dtype=np.float64)
    if caps.ndim != 1 or caps.size == 0:
        raise ValueError(f"caps must be a non-empty 1-D sweep, "
                         f"got shape {caps.shape}")
    b0 = dead_pin_mask(dead, problem.tau) if dead is not None else None
    base = problem.node_lp(cost_cap=float(caps[0]), b_fixed0=b0)
    return [base._replace(cap=float(ck)) for ck in caps]


@dataclasses.dataclass
class TenantFrontier:
    """One tenant's slice of a merged stacked solve: the LP lower-bound
    latency-cost frontier over its budget sweep, plus the relaxed
    allocations (share fractions, usable directly for divisible
    workloads or as B&B warm starts)."""
    caps: np.ndarray              # (K,) budget sweep
    makespans: np.ndarray         # (K,) LP lower-bound makespans
    allocs: List[np.ndarray]      # K x (mu, tau) relaxed allocations
    converged: np.ndarray         # (K,) per-row IPM convergence

    def pareto_points(self):
        """(costs, makespans) of the non-dominated sweep points (the
        caps are the cost budgets; makespans are the LP bounds)."""
        mask = pareto_filter(self.caps, self.makespans)
        return self.caps[mask], self.makespans[mask]


def tenant_frontiers(problems, caps_list, sol) -> List[TenantFrontier]:
    """Slice a MERGED stacked :class:`~repro.core.lp.LPSolution` back
    into per-tenant frontiers.

    ``sol`` must hold the tenants' rows tenant-major in submission
    order — tenant ``i``'s rows occupy the contiguous slice starting at
    ``sum(len(caps_list[:i]))`` — which is exactly how the serving
    scheduler (and :func:`repro.core.lp.solve_node_lps_ladder`) lays
    them out.  Rows are independent under ``vmap``, so each slice is
    identical to what a solo stacked solve of that tenant's sweep
    returns (to the last ulp for numerically stable rows, <= 1e-8 for
    ill-conditioned stragglers under the chunked driver).
    """
    # one transfer for all three fields: sol may hold device arrays (the
    # device-compacted chunked driver returns them), and three separate
    # np.asarray calls would issue three blocking copies
    xs, objs, conv = (np.asarray(v) for v in
                      jax.device_get((sol.x, sol.obj, sol.converged)))
    total = sum(len(c) for c in caps_list)
    if xs.shape[0] < total:
        raise ValueError(f"merged solution has {xs.shape[0]} rows, "
                         f"tenants claim {total}")
    out, off = [], 0
    for p, caps in zip(problems, caps_list):
        caps = np.asarray(caps, dtype=np.float64)
        k = len(caps)
        sl = slice(off, off + k)
        allocs = [p.split_node_x(xs[j])[0] for j in range(off, off + k)]
        out.append(TenantFrontier(caps, objs[sl].copy(), allocs,
                                  conv[sl].copy()))
        off += k
    return out


# ---------------------------------------------------------------------------
# Scenario sweeps: one frontier per scenario through one batched solve
# ---------------------------------------------------------------------------

def _as_scenario_set(scenarios):
    from repro.core.scenarios import Scenario, ScenarioSet
    if isinstance(scenarios, ScenarioSet):
        return scenarios
    if isinstance(scenarios, Scenario):
        return ScenarioSet((scenarios,))
    return ScenarioSet(tuple(scenarios))


def _batched_scenario_relaxation(probs, caps_list, dead_masks,
                                 linsolve: str = "xla",
                                 compact: bool = False,
                                 chunk_iters: Optional[int] = None,
                                 newton_dtype: str = "float64",
                                 mesh=None, row_spec=None):
    """One stacked IPM call across every (scenario, budget) pair.

    Returns (lbs (S, K), relax_allocs (S, K) list-of-lists, bounds
    (S, K)); ``bounds`` are the lbs usable as B&B lower bounds
    (:func:`_trusted_bounds`).  Dead
    platforms are pinned to zero allocation via the node's variable
    bounds, not just the latency penalty.  ``mesh`` shards the
    (scenario x budget) row axis over a device mesh — this megabatch is
    exactly the embarrassingly row-parallel workload sharding targets.
    """
    from repro.core import lp as lpmod
    nodes = []
    for p, caps, dead in zip(probs, caps_list, dead_masks):
        nodes.extend(frontier_nodes(p, caps, dead))
    sols = lpmod.solve_node_lps_stacked(nodes, linsolve=linsolve,
                                        compact=compact,
                                        chunk_iters=chunk_iters,
                                        newton_dtype=newton_dtype,
                                        mesh=mesh, row_spec=row_spec)
    s, k = len(probs), len(caps_list[0])
    lbs = np.asarray(sols.obj).reshape(s, k)
    bounds = _trusted_bounds(lbs, np.asarray(sols.converged).reshape(s, k))
    xs = np.asarray(sols.x).reshape(s, k, -1)
    allocs = [[probs[i].split_node_x(xs[i, j])[0] for j in range(k)]
              for i in range(s)]
    return lbs, allocs, bounds


def _trusted_bounds(lbs, converged) -> np.ndarray:
    """Relaxation objectives a B&B may take as lower bounds: the
    objective of a row the IPM did not converge bounds nothing (it can
    sit above the optimum and would close a tree at a wrong incumbent),
    so such rows become ``-inf`` — no bound."""
    return np.where(np.asarray(converged, bool), np.asarray(lbs, float),
                    -np.inf)


# sweep kwargs that also steer the batched relaxation solves: extracted
# from a caller's **kw (which is otherwise forwarded to solve_bnb_sweep)
def _stacked_solve_kw(kw: dict) -> dict:
    return dict(linsolve=kw.get("linsolve", "xla"),
                compact=kw.get("compact", False),
                chunk_iters=kw.get("chunk_iters"),
                newton_dtype=kw.get("newton_dtype", "float64"),
                mesh=kw.get("mesh"), row_spec=kw.get("row_spec"))


# kwargs safe to forward to the B&B engine: mesh sharding steers only the
# big stacked relaxation megabatches — the lockstep node batches inside
# solve_bnb_sweep are narrow (batch_width rows) and stay unsharded
def _bnb_kw(kw: dict) -> dict:
    return {k: v for k, v in kw.items() if k not in ("mesh", "row_spec")}


def scenario_relaxation_frontiers(problem: AllocationProblem, scenarios,
                                  n_points: int = 8,
                                  linsolve: str = "xla",
                                  compact: bool = False,
                                  chunk_iters: Optional[int] = None,
                                  newton_dtype: str = "float64",
                                  mesh=None, row_spec=None):
    """LP-relaxation (lower-bound) frontier per scenario, ALL scenarios
    and budget points solved in a single batched interior-point call.

    Returns ``{scenario_name: (caps, lb_makespans)}``.  This is the
    cheap path for "how would the frontier move if ..." what-if queries:
    no branch & bound at all.
    """
    scen = _as_scenario_set(scenarios)
    probs = scen.problems(problem)
    caps_list = [np.linspace(*_cheap_cost_bounds(p, s.dead), n_points)
                 for p, s in zip(probs, scen)]
    lbs, _, _ = _batched_scenario_relaxation(
        probs, caps_list, [s.dead for s in scen], linsolve=linsolve,
        compact=compact, chunk_iters=chunk_iters,
        newton_dtype=newton_dtype, mesh=mesh, row_spec=row_spec)
    return {s.name: (caps_list[i], lbs[i]) for i, s in enumerate(scen)}


def scenario_frontiers(problem: AllocationProblem, scenarios,
                       n_points: int = 8, **kw):
    """Exact (B&B) Pareto frontier per scenario in one call.

    The relaxations of every (scenario, budget) pair are solved as ONE
    batched IPM call; each scenario's sweep then runs the warm-started
    B&B path of :func:`milp_tradeoff_batched`.  Returns
    ``{scenario_name: Tradeoff}``.
    """
    scen = _as_scenario_set(scenarios)
    probs = scen.problems(problem)
    bounds = [cost_bounds_batched(p, **_bnb_kw(kw)) for p in probs]
    caps_list = [np.linspace(c_l, max(c_u, c_l), n_points)
                 for c_l, c_u, _ in bounds]
    _, relax_allocs, lbs = _batched_scenario_relaxation(
        probs, caps_list, [s.dead for s in scen], **_stacked_solve_kw(kw))
    out = {}
    for i, s in enumerate(scen):
        c_l, c_u, top = bounds[i]
        points = _warm_sweep(probs[i], caps_list[i], lbs[i],
                             relax_allocs[i], top, **_bnb_kw(kw))
        points.append(TradeoffPoint(None, top.makespan, top.cost, top.alloc,
                                    dict(status=top.status, nodes=top.nodes,
                                         lb=top.lower_bound)))
        out[s.name] = Tradeoff(points, c_l, c_u, "milp-bnb-batched")
    return out


def _cheap_cost_bounds(problem: AllocationProblem, dead=None):
    """Closed-form budget anchors (no MILP): cheapest single platform to
    the realised cost of a latency-weighted proportional split.  Dead
    platforms (scenario failures) are excluded from both anchors."""
    lat = problem.single_platform_latency()
    cost = problem.single_platform_cost()
    alive = np.ones(problem.mu, dtype=bool)
    if dead is not None and np.asarray(dead).any():
        alive = ~np.asarray(dead, bool)
    c_l = float(cost[alive].min())
    w = np.where(alive, 1.0 / lat, 0.0)
    split = heuristics.proportional_split(problem, w)
    _, c_split = heuristics.evaluate(problem, split)
    return c_l, max(c_l, float(c_split))


def heuristic_tradeoff(problem: AllocationProblem, n_points: int = 8
                       ) -> Tradeoff:
    """The paper's heuristic competitor: scalarisation-weight sweep."""
    c_l = float(problem.single_platform_cost().min())
    points = []
    for lam in np.linspace(0.0, 1.0, max(n_points, 2)):
        alloc = heuristics.scalarised(problem, float(lam))
        mk, cost = heuristics.evaluate(problem, alloc)
        points.append(TradeoffPoint(None, mk, cost, alloc, dict(lam=lam)))
    cheap = heuristics.cheapest_single_platform(problem)
    mk, cost = heuristics.evaluate(problem, cheap)
    points.append(TradeoffPoint(None, mk, cost, cheap, dict(lam=1.0)))
    c_u = max(p.cost for p in points)
    return Tradeoff(points, c_l, c_u, "heuristic")
