"""MILP partitioner: structure-exploiting branch & bound (primary) and
scipy/HiGHS on the untransformed Eq. 4 (oracle / very-large-scale backend).

The B&B exploits two observations about Eq. 4 (see DESIGN.md §2):

* in the LP relaxation, the setup binary B appears only through
  ``+gamma*B`` in the platform latency with the coupling ``A <= B``;
  since gamma >= 0, any LP optimum has B = A, so free binaries can be
  *substituted out*.  Node LPs therefore have mu*tau + mu + 1 variables
  and ~tau + 2mu + 1 rows instead of ~tau + 2*mu*tau + mu + 1 rows.
* the quanta integer D only enters via the budget row; its relaxation is
  D = G_L / rho, substituted likewise and branched on only when the
  budget row is binding at a fractional D.

Node LPs are solved by the jit-compiled JAX interior-point method
(:mod:`repro.core.lp`); shapes are identical across nodes, so the jit
cache holds a bounded, flat set of solver variants per problem size —
one per dispatch width under the monolithic driver (a lockstep sweep
has two, see :func:`solve_bnb_sweep`), and one per power-of-two ladder
width under the chunked ``compact=True`` driver
(``lp.stacked_compile_count`` tracks it).  Nodes whose IPM solve does
not converge cleanly are re-solved with HiGHS (robust infeasibility
certificates); the ``milp.host_resolves`` counter counts them.
"""
from __future__ import annotations

import dataclasses
import heapq
import itertools
import time
from typing import Optional, Tuple

import numpy as np

from repro import obs
from repro.core import heuristics
from repro.core import lp as lpmod
from repro.core.problem import AllocationProblem

_FRAC_TOL = 1e-6
_FEAS_TOL = 1e-9


@dataclasses.dataclass
class MILPResult:
    alloc: Optional[np.ndarray]
    makespan: float
    cost: float
    lower_bound: float
    status: str                  # optimal | feasible | infeasible | node_limit
    nodes: int
    backend: str
    wall_s: float

    @property
    def gap(self) -> float:
        if self.alloc is None or self.lower_bound <= 0:
            return np.inf
        return (self.makespan - self.lower_bound) / self.makespan


# ---------------------------------------------------------------------------
# Node LP solve (JAX IPM with HiGHS fallback)
# ---------------------------------------------------------------------------

def _solve_node(node, prefer_jax: bool = True, linsolve: str = "xla",
                newton_dtype: str = "float64"):
    """Returns (x, obj, status) with status in {ok, infeasible}.  Every
    node LP that ends on the HiGHS host path counts in the
    ``milp.host_resolves`` counter, so a device solver that stops
    converging shows up instead of hiding behind correct frontiers."""
    if prefer_jax:
        sol = lpmod.solve_node_lp(node, linsolve=linsolve,
                                  newton_dtype=newton_dtype)
        if bool(sol.converged):
            return np.asarray(sol.x), float(sol.obj), "ok"
    obs.inc("milp.host_resolves")
    with obs.span("milp.host_resolve"):
        a_eq, g = node.sparse_rows()
        res = lpmod.scipy_reference_lp(node.c, a_eq, node.b_eq, g, node.h,
                                       node.lb, node.ub)
    if res.status == 2:
        return None, np.inf, "infeasible"
    if not res.success:
        return None, np.inf, "infeasible"
    return res.x, float(res.fun), "ok"


def _round_incumbent(problem: AllocationProblem, a: np.ndarray,
                     cost_cap: Optional[float],
                     allowed: Optional[np.ndarray] = None):
    """Round an LP allocation to a feasible incumbent (true models).
    ``allowed`` keeps the budget repair off pinned/dead platforms."""
    a = np.maximum(a, 0.0)
    a = a / np.maximum(a.sum(axis=0, keepdims=True), 1e-12)
    a[a < 1e-9] = 0.0
    a = a / np.maximum(a.sum(axis=0, keepdims=True), 1e-12)
    mk, cost = heuristics.evaluate(problem, a)
    if cost_cap is not None and cost > cost_cap * (1 + _FEAS_TOL):
        repaired = heuristics.repair_to_budget(problem, a, cost_cap,
                                               allowed=allowed)
        if repaired is None:
            return None, np.inf, np.inf
        a = repaired
        mk, cost = heuristics.evaluate(problem, a)
    return a, mk, cost


# ---------------------------------------------------------------------------
# Branch & bound
# ---------------------------------------------------------------------------

def _expand_node(problem: AllocationProblem, nd: dict, x: np.ndarray,
                 obj: float, cost_cap: Optional[float], heap: list,
                 counter, gap_tol: float
                 ) -> Tuple[Optional[np.ndarray], float, float]:
    """Process a solved, un-pruned node: derive an incumbent candidate and
    push branched children.  Returns the (cand, mk, cost) incumbent
    candidate (cand is None when rounding/repair fails).

    A node whose relaxation is integral within ``_FRAC_TOL`` is closed
    only when its rounded candidate is within ``gap_tol`` of the
    relaxation: a share under ``_FRAC_TOL`` costs the relaxation
    ``gamma`` times the share but the candidate its whole setup, so
    otherwise the node branches on the largest such share."""
    a, d, _ = problem.split_node_x(x)
    # rows with every setup binary fixed to 0 (root pin or branching)
    # cannot take work; keep the budget repair off them as well
    dead_rows = nd["b0"].all(axis=1)
    cand, mk, cost = _round_incumbent(
        problem, a, cost_cap,
        allowed=None if not dead_rows.any() else ~dead_rows)

    # pick a branch variable: setup binaries first, then quanta
    free = ~(nd["b0"] | nd["b1"])
    frac_b = np.where(free, problem.gamma * a * (1.0 - a), 0.0)
    # only A strictly inside (0,1) matters
    inside = (a > _FRAC_TOL) & (a < 1 - _FRAC_TOL)
    frac_b = np.where(inside, frac_b, 0.0)
    bi, bj = np.unravel_index(int(np.argmax(frac_b)), frac_b.shape)
    b_score = frac_b[bi, bj]

    d_frac = d - np.floor(d)
    d_score_vec = problem.pi * np.minimum(d_frac, 1 - d_frac)
    d_i = int(np.argmax(d_score_vec))
    d_score = d_score_vec[d_i] if cost_cap is not None else 0.0

    if b_score <= _FRAC_TOL and d_score <= _FRAC_TOL:
        if cand is None or mk <= obj * (1 + gap_tol):
            # relaxation is integral-enough: node is solved exactly
            return cand, mk, cost
        # the candidate pays setups the relaxation barely charges: branch
        # on the binary of the share that costs most of them
        frac_b = np.where(free & (a > 0) & (a < 1),
                          problem.gamma * a * (1.0 - a), 0.0)
        bi, bj = np.unravel_index(int(np.argmax(frac_b)), frac_b.shape)
        if frac_b[bi, bj] <= 0:
            return cand, mk, cost
        b_score, d_score = frac_b[bi, bj], 0.0

    if b_score >= d_score:
        for val in (1, 0):
            child = dict(b0=nd["b0"].copy(), b1=nd["b1"].copy(),
                         d_lb=nd["d_lb"].copy(),
                         d_ub=None if nd["d_ub"] is None else nd["d_ub"].copy())
            (child["b1"] if val else child["b0"])[bi, bj] = True
            heapq.heappush(heap, (obj, next(counter), child))
    else:
        lo = dict(b0=nd["b0"].copy(), b1=nd["b1"].copy(),
                  d_lb=nd["d_lb"].copy(),
                  d_ub=(problem.d_max() if nd["d_ub"] is None
                        else nd["d_ub"].copy()))
        lo["d_ub"][d_i] = np.floor(d[d_i])
        hi = dict(b0=nd["b0"].copy(), b1=nd["b1"].copy(),
                  d_lb=nd["d_lb"].copy(),
                  d_ub=None if nd["d_ub"] is None else nd["d_ub"].copy())
        hi["d_lb"][d_i] = np.ceil(d[d_i])
        heapq.heappush(heap, (obj, next(counter), lo))
        heapq.heappush(heap, (obj, next(counter), hi))
    return cand, mk, cost


def _project_to_allocation(problem: AllocationProblem, a: np.ndarray,
                           allowed: Optional[np.ndarray] = None
                           ) -> np.ndarray:
    """Project an arbitrary warm-start matrix onto the feasible set
    (non-negative, every task column summing to 1).  Columns with no
    mass — e.g. shares stranded on a failed platform — are refilled
    latency-proportionally; evaluate() silently under-counts unassigned
    tasks, so an unprojected warm start could fake an incumbent bound.
    ``allowed`` (mu,) restricts the projection to a subset of platforms
    (pinned/dead rows are zeroed and excluded from refills)."""
    a = np.maximum(np.asarray(a, dtype=np.float64), 0.0)
    if allowed is not None:
        a = np.where(np.asarray(allowed, bool)[:, None], a, 0.0)
    colsum = a.sum(axis=0)
    empty = colsum <= 1e-9
    if empty.any():
        w = 1.0 / problem.single_platform_latency()
        if allowed is not None:
            w = np.where(allowed, w, 0.0)
        a[:, empty] = (w / w.sum())[:, None]
        colsum = a.sum(axis=0)
    return a / colsum[None, :]


def _seed_incumbent(problem: AllocationProblem, cost_cap: Optional[float],
                    warm_alloc: Optional[np.ndarray] = None,
                    pinned: Optional[np.ndarray] = None
                    ) -> Tuple[Optional[np.ndarray], float, float]:
    """Root incumbent: the heuristic battery, plus the warm-start
    allocation when given (repaired into budget if it overshoots) — warm
    starts strengthen the seed, never replace it.  ``pinned`` is the
    root's b_fixed0 mask; platforms whose every setup binary is pinned to
    zero (dead/empty slots) are stripped from every candidate.  Each call
    is one ``milp.seed`` span."""
    with obs.span("milp.seed"):
        incumbent, inc_mk, inc_cost = None, np.inf, np.inf
        allowed = None
        if pinned is not None:
            allowed = ~np.asarray(pinned, bool).all(axis=1)
        if cost_cap is None:
            cand = heuristics.proportional_split(problem)
            cand_list = [cand, heuristics.min_min(problem)]
        else:
            cand_list = []
            h = heuristics.best_heuristic_for_budget(problem, cost_cap)
            if h is not None:
                cand_list.append(h)
        if warm_alloc is not None:
            cand_list.append(_project_to_allocation(problem, warm_alloc,
                                                    allowed))
        for cand in cand_list:
            if allowed is not None:
                cand = _project_to_allocation(problem, cand, allowed)
            mk, cost = heuristics.evaluate(problem, cand)
            if cost_cap is not None and cost > cost_cap * (1 + _FEAS_TOL):
                cand = heuristics.repair_to_budget(problem, cand, cost_cap,
                                                   allowed=allowed)
                if cand is None:
                    continue
                mk, cost = heuristics.evaluate(problem, cand)
            if (cost_cap is None or cost <= cost_cap * (1 + _FEAS_TOL)) and mk < inc_mk:
                incumbent, inc_mk, inc_cost = cand, mk, cost
        return incumbent, inc_mk, inc_cost


def solve_bnb(problem: AllocationProblem, cost_cap: Optional[float] = None,
              *, node_limit: int = 2000, gap_tol: float = 1e-4,
              time_limit_s: float = 120.0, prefer_jax: bool = True,
              warm_alloc: Optional[np.ndarray] = None,
              lower_bound0: Optional[float] = None,
              pinned: Optional[np.ndarray] = None,
              linsolve: str = "xla",
              newton_dtype: str = "float64"
              ) -> MILPResult:
    """Structure-exploiting branch & bound.

    ``warm_alloc`` seeds the incumbent (e.g. the neighbouring budget
    point's optimum during a Pareto sweep — repaired into this budget if
    it overshoots).  ``lower_bound0`` is a known global lower bound, e.g.
    this cap's entry from the batched LP-relaxation sweep
    (:func:`repro.core.pareto.relaxation_frontier`); when the warm
    incumbent already meets it within ``gap_tol`` the solve returns
    immediately with zero nodes.  ``pinned`` is a (mu, tau) bool mask of
    setup binaries fixed to 0 at the ROOT (inherited by every node) —
    dead platforms / empty fleet slots, see
    :func:`repro.core.scenarios.dead_pin_mask`.  ``linsolve`` picks the
    node LPs' Newton linear-system backend (:data:`repro.core.lp.LINSOLVES`)
    and ``newton_dtype`` its precision (:data:`repro.core.lp.NEWTON_DTYPES`).
    """
    t0 = time.monotonic()
    mu, tau = problem.mu, problem.tau

    incumbent, inc_mk, inc_cost = _seed_incumbent(problem, cost_cap,
                                                  warm_alloc, pinned)
    lb0 = -np.inf if lower_bound0 is None else float(lower_bound0)
    if incumbent is not None and inc_mk <= max(lb0, 0.0) * (1 + gap_tol):
        # warm incumbent already optimal within tolerance: no search needed
        return MILPResult(incumbent, inc_mk, inc_cost, lb0, "optimal", 0,
                          "bnb-jax", time.monotonic() - t0)

    counter = itertools.count()
    b0_root = (np.zeros((mu, tau), bool) if pinned is None
               else np.array(pinned, dtype=bool))
    root = dict(b0=b0_root, b1=np.zeros((mu, tau), bool),
                d_lb=np.zeros(mu), d_ub=None)
    heap = [(0.0, next(counter), root)]
    nodes = 0
    status = "optimal"

    while heap:
        if nodes >= node_limit:
            status = "node_limit"
            break
        if time.monotonic() - t0 > time_limit_s:
            status = "time_limit"
            break
        parent_lb, _, nd = heapq.heappop(heap)
        if parent_lb >= inc_mk * (1 - gap_tol):
            continue
        nodes += 1
        node = problem.node_lp(cost_cap, nd["b0"], nd["b1"],
                               nd["d_lb"], nd["d_ub"])
        x, obj, st = _solve_node(node, prefer_jax, linsolve, newton_dtype)
        if st == "infeasible":
            continue
        if obj >= inc_mk * (1 - gap_tol):
            continue
        cand, mk, cost = _expand_node(problem, nd, x, obj, cost_cap,
                                      heap, counter, gap_tol)
        if cand is not None and mk < inc_mk:
            incumbent, inc_mk, inc_cost = cand, mk, cost

    open_lb = min((lb for lb, _, _ in heap), default=np.inf)
    lower = max(min(open_lb, inc_mk), lb0) if np.isfinite(lb0) \
        else min(open_lb, inc_mk)
    if incumbent is None:
        return MILPResult(None, np.inf, np.inf, lower,
                          "infeasible" if status == "optimal" else status,
                          nodes, "bnb-jax", time.monotonic() - t0)
    if status == "optimal" and open_lb >= inc_mk * (1 - gap_tol):
        st = "optimal"
    elif status == "optimal":
        st = "optimal"
    else:
        st = status
    return MILPResult(incumbent, inc_mk, inc_cost, lower, st, nodes,
                      "bnb-jax", time.monotonic() - t0)


# ---------------------------------------------------------------------------
# Lockstep batched B&B across a budget sweep
# ---------------------------------------------------------------------------

# (node-LP shape, rungs, solver configuration) whose dispatch rungs this
# process has compiled
_WARM_RUNGS: set = set()


def _dispatch_rungs(batch_width: int) -> list:
    """The widths a lockstep round may be dispatched at, descending: the
    top two of the stacked solver's width ladder.  Each rung is one more
    compiled program per node-LP shape, so there are no finer ones."""
    return lpmod.ladder_widths(batch_width)[:2]


def _warm_rungs(node, rungs: list, **solver) -> None:
    """Compile every rung for ``node``'s shape and ``solver``
    configuration, once per process, with all-retired calls
    (:func:`repro.core.lp.warm_ladder`)."""
    key = (np.shape(node.coef), np.shape(node.h), tuple(rungs),
           tuple(sorted(solver.items())))
    if key not in _WARM_RUNGS:
        lpmod.warm_ladder(node, rungs[0], widths=rungs, **solver)
        _WARM_RUNGS.add(key)


def solve_bnb_sweep(problem: AllocationProblem, caps,
                    *, node_limit: int = 2000, gap_tol: float = 1e-4,
                    time_limit_s: float = 120.0,
                    warm_allocs=None, lower_bounds0=None,
                    batch_width: Optional[int] = None,
                    lp_tol: float = 1e-7,
                    prefer_jax: bool = True,
                    pinned: Optional[np.ndarray] = None,
                    linsolve: str = "xla",
                    early_exit: bool = True,
                    compact: bool = False,
                    chunk_iters: Optional[int] = None,
                    newton_dtype: str = "float64") -> list:
    """Run one B&B tree per budget cap IN LOCKSTEP: each round pops the
    best open node from every active tree and solves all node relaxations
    as a single batched interior-point call
    (:func:`repro.core.lp.solve_node_lps_stacked`).  Node shapes are
    identical across trees, and closed trees are padded out of the batch.
    A round is dispatched at the smaller of two rungs that holds its
    nodes: the batch width, or the next width below it on
    :func:`repro.core.lp.ladder_widths` (16 and 8 at width 16).  A round
    that pops half a batch or less thus pays half the rows: a vmapped
    solve computes every row, retired or not, until its slowest row
    converges.  The node-LP shape then has two compiled programs, both
    compiled before the first round of the first sweep of that shape and
    solver configuration in the process, so no later sweep compiles.

    Incumbents propagate across trees between rounds: an allocation found
    by one budget point seeds every other point whose budget it fits
    (with greedy repair toward tighter budgets), which is what lets most
    trees close at — or near — the root.

    ``warm_allocs`` / ``lower_bounds0`` (one entry per cap, e.g. from the
    batched LP-relaxation sweep) seed incumbents and global lower bounds.
    ``batch_width`` is the stacked-IPM width per round (default
    ``min(max(2 * n_caps, 8), 64)``): each round's batch is refilled by
    best-bound priority across ALL open trees (a lone hard tree can fill
    the whole batch), and the solved rows are then processed in
    best-bound order with incumbents propagating between rows — so a
    strong incumbent discovered by the best node of a round prunes its
    weaker batch-mates immediately instead of one round later.
    ``pinned`` (mu, tau) pins setup binaries to zero at every tree's root
    (dead platforms / empty fleet slots).  ``time_limit_s`` covers the
    whole sweep.  Returns a list of :class:`MILPResult`, one per cap, in
    input order.

    ``linsolve`` picks the stacked IPM's Newton backend
    (:data:`repro.core.lp.LINSOLVES`).  With ``early_exit`` (default on)
    each round's batch is compacted: the popped nodes occupy the leading
    rows and the padding up to the rung is marked inactive via the solver's
    ``row_active`` mask, so retired rows are charged zero Newton
    iterations in the ``lp.newton_row_stats`` ledger instead of
    duplicating row 0's whole solve.  Note the ledger counts *useful*
    work: a vmapped ``while_loop`` on CPU still computes (and
    select-masks) every SIMD row each trip, so early exit does not
    change wall clock there — it quantifies exactly the work a
    lane-skipping accelerator backend avoids, and the work the chunked
    ``compact=True`` driver below reclaims as wall clock.  Active rows'
    iterates are bit-identical either way (rows of
    a vmapped solve are independent), which the regression tests in
    ``tests/test_milp.py`` assert.  The mask is traced, so early exit
    never recompiles (``lp.stacked_compile_count`` stays flat as rows
    retire mid-sweep).

    ``compact`` / ``chunk_iters`` switch every round's stacked solve to
    the CHUNKED driver (mid-call batch compaction,
    :func:`repro.core.lp.solve_lp_stacked`): converged rows stop paying
    while-loop trips mid-call, which turns the early-exit ledger's saved
    Newton rows into wall-clock speedup on lockstep (CPU) backends.
    ``newton_dtype="float32"`` additionally runs the Newton solves on
    the mixed-precision path (f32 + one f64 refinement step, per-row
    f64 fallback).
    """
    t0 = time.monotonic()
    caps = [None if c is None else float(c) for c in caps]
    k = len(caps)
    if k == 0:
        return []
    if any(c is None for c in caps) and not all(c is None for c in caps):
        # a capless node LP has no budget row, so its shape differs and
        # the batch could not be stacked
        raise ValueError("cannot mix cost-capped and uncapped sweeps")
    if batch_width is None:
        batch_width = min(max(2 * k, 8), 64)
    batch_width = max(batch_width, 1)
    if warm_allocs is None:
        warm_allocs = [None] * k
    if lower_bounds0 is None:
        lower_bounds0 = [None] * k
    mu, tau = problem.mu, problem.tau
    rungs = _dispatch_rungs(batch_width)
    solver = dict(linsolve=linsolve, compact=compact,
                  chunk_iters=chunk_iters, newton_dtype=newton_dtype)
    if len(rungs) > 1:
        _warm_rungs(problem.node_lp(caps[0]), rungs, **solver)

    trees = []
    for cap, warm, lb0 in zip(caps, warm_allocs, lower_bounds0):
        inc, mk, cost = _seed_incumbent(problem, cap, warm, pinned)
        tr = dict(cap=cap, heap=[], counter=itertools.count(),
                  incumbent=inc, inc_mk=mk, inc_cost=cost, nodes=0,
                  status=None,
                  lb0=-np.inf if lb0 is None else float(lb0))
        if inc is not None and mk <= max(tr["lb0"], 0.0) * (1 + gap_tol):
            tr["status"] = "optimal"
        else:
            root = dict(b0=(np.zeros((mu, tau), bool) if pinned is None
                            else np.array(pinned, dtype=bool)),
                        b1=np.zeros((mu, tau), bool),
                        d_lb=np.zeros(mu), d_ub=None)
            tr["heap"] = [(0.0, next(tr["counter"]), root)]
        trees.append(tr)

    allowed_rows = (None if pinned is None
                    else ~np.asarray(pinned, bool).all(axis=1))

    def propagate(mk, cost, cand):
        """Offer an incumbent to every tree whose budget it (nearly) fits."""
        for tr in trees:
            if mk >= tr["inc_mk"]:
                continue
            if tr["cap"] is None or cost <= tr["cap"] * (1 + _FEAS_TOL):
                tr["incumbent"], tr["inc_mk"], tr["inc_cost"] = cand, mk, cost
            elif mk < tr["inc_mk"] * 0.999:
                # over budget: greedy repair, but only when the candidate
                # promises a real improvement (repair is the hot path)
                fixed = heuristics.repair_to_budget(problem, cand, tr["cap"],
                                                    allowed=allowed_rows)
                if fixed is None:
                    continue
                mk2, cost2 = heuristics.evaluate(problem, fixed)
                if mk2 < tr["inc_mk"]:
                    tr["incumbent"] = fixed
                    tr["inc_mk"], tr["inc_cost"] = mk2, cost2

    for tr in trees:
        if tr["incumbent"] is not None:
            propagate(tr["inc_mk"], tr["inc_cost"], tr["incumbent"])

    rounds = 0
    while True:
        timed_out = time.monotonic() - t0 > time_limit_s
        for tr in trees:
            if tr["status"] is not None:
                continue
            if timed_out:
                tr["status"] = "time_limit"
            elif tr["nodes"] >= node_limit:
                tr["status"] = "node_limit"
            elif not tr["heap"]:
                # children were either never created or all pruned
                tr["status"] = "optimal"
        if timed_out:
            break

        # Fill the fixed batch width best-first across ALL open trees, so
        # a lone hard tree still explores batch_width nodes per round
        # instead of 1.
        popped = []
        pops = {id(tr): 0 for tr in trees}
        while len(popped) < batch_width:
            best = None
            for tr in trees:
                if (tr["status"] is not None or not tr["heap"]
                        or tr["nodes"] + pops[id(tr)] >= node_limit):
                    continue
                if best is None or tr["heap"][0][0] < best["heap"][0][0]:
                    best = tr
            if best is None:
                break
            lb, _, nd = heapq.heappop(best["heap"])
            if lb >= best["inc_mk"] * (1 - gap_tol):
                continue
            pops[id(best)] += 1
            popped.append((best, nd))
        if not popped:
            break

        rounds += 1
        width = min(w for w in rungs if w >= len(popped))
        with obs.span("milp.round", round=rounds, popped=len(popped),
                      width=batch_width, dispatch_width=width) as round_span:
            with obs.span("milp.assemble"):
                lps = [problem.node_lp(tr["cap"], nd["b0"], nd["b1"],
                                       nd["d_lb"], nd["d_ub"])
                       for tr, nd in popped]
                # pad with row 0 to the rung, whose program is compiled.
                # lp_tol ~ 1e-7 (vs the 1e-9 reference default): node
                # solves only need bounding accuracy well inside gap_tol,
                # and the whole batch iterates until its SLOWEST member
                # converges.
                batch = lps + [lps[0]] * (width - len(lps))
                active = None
                if early_exit:
                    active = np.arange(width) < len(lps)
            sols = lpmod.solve_node_lps_stacked(batch, tol=lp_tol,
                                                row_active=active, **solver)
            with obs.span("milp.fetch"):
                xs = np.asarray(sols.x)
                objs = np.asarray(sols.obj)
                conv = np.asarray(sols.converged)

            # Process rows in best-bound order (non-converged rows, which
            # need an eager HiGHS re-solve for a trusted bound, go last):
            # incumbents found by the round's strongest nodes then prune
            # the weaker batch-mates below, instead of going stale for a
            # round.
            with obs.span("milp.expand"):
                inc_updates = 0
                order = sorted(range(len(popped)),
                               key=lambda r: (not conv[r], float(objs[r])))
                for row in order:
                    tr, nd = popped[row]
                    tr["nodes"] += 1
                    if conv[row]:
                        x, obj, st = xs[row], float(objs[row]), "ok"
                    else:
                        x, obj, st = _solve_node(lps[row], prefer_jax=False)
                    if st == "infeasible":
                        continue
                    if obj >= tr["inc_mk"] * (1 - gap_tol):
                        continue
                    cand, mk, cost = _expand_node(problem, nd, x, obj,
                                                  tr["cap"], tr["heap"],
                                                  tr["counter"], gap_tol)
                    if cand is not None and mk < tr["inc_mk"]:
                        tr["incumbent"], tr["inc_mk"], tr["inc_cost"] = \
                            cand, mk, cost
                        propagate(mk, cost, cand)
                        inc_updates += 1
            round_span.set(incumbent_updates=inc_updates)
        obs.update(counters={"milp.rounds": 1, "milp.nodes": len(popped),
                             "milp.batch_rows": batch_width,
                             "milp.dispatch_rows": width,
                             "milp.incumbent_updates": inc_updates})

    wall = time.monotonic() - t0
    out = []
    for tr in trees:
        open_lb = min((lb for lb, _, _ in tr["heap"]), default=np.inf)
        lower = min(open_lb, tr["inc_mk"])
        if np.isfinite(tr["lb0"]):
            lower = max(lower, tr["lb0"])
        status = tr["status"] or "optimal"
        if tr["incumbent"] is None:
            out.append(MILPResult(None, np.inf, np.inf, lower,
                                  "infeasible" if status == "optimal"
                                  else status,
                                  tr["nodes"], "bnb-jax-sweep", wall))
        else:
            out.append(MILPResult(tr["incumbent"], tr["inc_mk"],
                                  tr["inc_cost"], lower, status,
                                  tr["nodes"], "bnb-jax-sweep", wall))
    return out


# ---------------------------------------------------------------------------
# HiGHS backend on untransformed Eq. 4
# ---------------------------------------------------------------------------

def solve_highs(problem: AllocationProblem, cost_cap: Optional[float] = None,
                *, time_limit_s: float = 120.0, mip_rel_gap: float = 1e-4
                ) -> MILPResult:
    from scipy.optimize import LinearConstraint, milp
    from scipy.sparse import csr_matrix

    t0 = time.monotonic()
    arrs = problem.full_milp_arrays(cost_cap)
    constraints = [
        LinearConstraint(csr_matrix(arrs["a_ub"]), -np.inf, arrs["b_ub"]),
        LinearConstraint(csr_matrix(arrs["a_eq"]), arrs["b_eq"], arrs["b_eq"]),
    ]
    from scipy.optimize import Bounds
    res = milp(c=arrs["c"], constraints=constraints,
               integrality=arrs["integrality"],
               bounds=Bounds(arrs["lb"], arrs["ub"]),
               options=dict(time_limit=time_limit_s, mip_rel_gap=mip_rel_gap))
    wall = time.monotonic() - t0
    if res.status == 2:
        return MILPResult(None, np.inf, np.inf, np.inf, "infeasible", 0,
                          "highs", wall)
    if res.x is None:
        # time limit with no incumbent — NOT proven infeasible.  The
        # problem always admits the best-heuristic construction whenever
        # the budget does, so fall back to it (paper step 2: at C_L both
        # methods coincide on the cheapest platform anyway).
        if cost_cap is not None:
            h = heuristics.best_heuristic_for_budget(problem, cost_cap)
        else:
            h = heuristics.proportional_split(problem)
        if h is None:
            return MILPResult(None, np.inf, np.inf, np.inf, "infeasible",
                              0, "highs", wall)
        mk, cost = heuristics.evaluate(problem, h)
        return MILPResult(h, mk, cost, 0.0, "time_limit_heuristic", 0,
                          "highs", wall)
    idx = arrs["idx"]
    a = res.x[idx["a"]:idx["b"]].reshape(problem.mu, problem.tau)
    a = np.maximum(a, 0.0)
    a = a / np.maximum(a.sum(axis=0, keepdims=True), 1e-12)
    mk, cost = heuristics.evaluate(problem, a)
    lb = res.mip_dual_bound if res.mip_dual_bound is not None else mk
    status = "optimal" if res.status == 0 else "feasible"
    return MILPResult(a, mk, cost, float(lb), status,
                      int(getattr(res, "mip_node_count", 0) or 0), "highs", wall)


def solve(problem: AllocationProblem, cost_cap: Optional[float] = None,
          backend: str = "bnb", **kw) -> MILPResult:
    if backend == "bnb":
        return solve_bnb(problem, cost_cap, **kw)
    if backend == "highs":
        return solve_highs(problem, cost_cap, **kw)
    raise ValueError(f"unknown backend {backend!r}")
