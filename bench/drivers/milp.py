"""Back-to-back exact trade-off sweeps through the lockstep B&B.

Each sweep is ``pareto.milp_tradeoff_batched`` on one of the deployment's
fitted tenant problems, cycling over them: the unconstrained anchor,
one stacked relaxation over the budget grid, then one B&B tree per
budget in lockstep, each cut only by the mix's node limit.  The window
finishes the sweep in progress when it closes.

Besides the sweeps' answers, the window keeps what the stacked interior
point returned on the way: every row of each sweep's stacked relaxation,
and a sample, drawn from the seed, of the B&B node rows (with the node's
branching state).  The check compares those objectives, both ways, with
HiGHS on the same relaxations built from the deployment's arrays.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import data
from bench.reference import lp as ref_lp
from bench.reference import milp as ref_milp


@dataclasses.dataclass
class State:
    models: list
    mix: dict
    seed: int
    problems: list = None
    tenant: int = 0                 # the tenant of the sweep in progress
    relax: list = dataclasses.field(default_factory=list)
    nodes: list = dataclasses.field(default_factory=list)
    node_rows: int = 0              # node rows the window solved
    batches_since_relax: int = 0
    restore: list = dataclasses.field(default_factory=list)
    profile: object = None


def _sweep(problem, mix: dict, node_limit: int):
    from repro.core import pareto
    return pareto.milp_tradeoff_batched(
        problem, n_points=int(mix["n_points"]), node_limit=node_limit,
        time_limit_s=float("inf"), gap_tol=float(mix["gap_tol"]),
        newton_dtype=mix["newton_dtype"])


def setup(cfg: dict, mix: dict, seed: int, seconds: float, *, root=None,
          log=print) -> State:
    from repro.core.problem import AllocationProblem
    models = data.tenant_models(cfg, seed)
    st = State(models, mix, seed)
    st.problems = [AllocationProblem(m["beta"], m["gamma"], m["n"], m["rho"],
                                     m["pi"], m["names"]) for m in models]
    t0 = time.perf_counter()
    _sweep(st.problems[0], mix, int(mix["warm_node_limit"]))
    log(f"warm sweep (node limit {mix['warm_node_limit']}) in "
        f"{time.perf_counter() - t0:.3f} s")
    _observe(st)
    return st


def _observe(st: State) -> None:
    """Wrap the program's stacked relaxation and node-batch solves so the
    window keeps their answers; the wrapped calls return what the
    program's own return, untouched."""
    from repro.core import lp, pareto
    relax_fn = pareto.relaxation_frontier
    node_fn = lp.solve_node_lps_stacked
    draw = data.rng(st.seed, data.SAMPLE, 1)
    keep = int(st.mix["node_samples"])

    def relaxation_frontier(problem, caps, **kw):
        out = relax_fn(problem, caps, **kw)
        if len(out) == 3:            # (caps, objectives, solutions)
            st.relax.append((st.tenant, np.array(caps, dtype=float), out))
            st.batches_since_relax = 0
        return out

    def solve_node_lps_stacked(nodes, **kw):
        nodes = list(nodes)
        sol = node_fn(nodes, **kw)
        active = kw.get("row_active")
        for row in (range(len(nodes)) if active is None
                    else np.flatnonzero(active)):
            # a uniform sample of the window's node rows (reservoir)
            st.node_rows += 1
            slot = (len(st.nodes) if len(st.nodes) < keep
                    else int(draw.integers(st.node_rows)))
            if slot < keep:
                entry = (st.tenant, nodes[row], sol, int(row))
                if slot == len(st.nodes):
                    st.nodes.append(entry)
                else:
                    st.nodes[slot] = entry
        st.batches_since_relax += 1
        _trace_step(st)
        return sol

    st.restore = [(pareto, "relaxation_frontier", relax_fn),
                  (lp, "solve_node_lps_stacked", node_fn)]
    pareto.relaxation_frontier = relaxation_frontier
    lp.solve_node_lps_stacked = solve_node_lps_stacked


def trace_slice(st: State, profile) -> None:
    """A traced run profiles B&B rounds: from the return of the
    ``trace_after_node_batches``-th node batch after a sweep's relaxation
    to the return of the ``trace_node_batches``-th after that, so the
    slice opens on the host work between two rounds (the end of one, the
    node assembly of the next) and holds the next round whole."""
    st.profile = profile


def _trace_step(st: State) -> None:
    p = st.profile
    if p is None or not st.relax:
        return
    after = int(st.mix["trace_after_node_batches"])
    if st.batches_since_relax == after:
        p.start()
    elif st.batches_since_relax == after + int(st.mix["trace_node_batches"]):
        p.stop()


def window(st: State, seconds: float) -> dict:
    sweeps = []
    t0 = time.perf_counter()
    while (time.perf_counter() - t0 < seconds
           or (st.profile is not None and not st.profile.started)):
        if st.profile is not None and sweeps:
            # no B&B round followed the first sweep's relaxation: trace
            # this whole sweep instead
            st.profile.start()
        k = len(sweeps) % len(st.problems)
        st.tenant = k
        ts = time.perf_counter()
        front = _sweep(st.problems[k], st.mix, int(st.mix["node_limit"]))
        te = time.perf_counter()
        pts = [dict(cap=p.cost_cap, makespan=p.makespan, cost=p.cost,
                    alloc=np.asarray(p.alloc), lb=p.meta["lb"],
                    status=p.meta["status"])
               for p in front.points if p.cost_cap is not None]
        sweeps.append(dict(tenant=k, t0=ts, t1=te, points=pts))
    t_end = time.perf_counter()
    failed = sum(len(s["points"]) < int(st.mix["n_points"]) for s in sweeps)
    return dict(attempted=len(sweeps), failed=failed, sweeps=sweeps, t0=t0,
                t_end=t_end)


def end_to_end(raw: dict) -> dict:
    return {"milp_sweep_s": (raw["t_end"] - raw["t0"]) / len(raw["sweeps"])}


def release(st: State) -> None:
    for mod, name, fn in st.restore:
        setattr(mod, name, fn)
    st.restore = []
    st.problems = None


def relaxation_rows(st: State) -> list:
    """(tenant, cap, objective) of every converged row of the window's
    stacked relaxations: the rows the program takes as bounds."""
    out = []
    for k, caps, (_, objs, sols) in st.relax:
        conv = np.asarray(sols.converged)
        out += [(k, float(c), float(o))
                for c, o, ok in zip(caps, np.asarray(objs), conv) if ok]
    return out


def node_fixings(m: dict, node) -> dict:
    """The branching state of one node LP: its budget, the setup binaries
    fixed to 0 (shares bounded to 0) and to 1 (latency rows that charge a
    share beta n alone), and the bounds on the quanta."""
    mu, tau = m["beta"].shape
    n_a = mu * tau
    g, lb, ub = (np.asarray(v) for v in (node.g, node.lb, node.ub))
    bn = m["beta"] * m["n"][None, :]
    coef = np.stack([g[i, i * tau:(i + 1) * tau] for i in range(mu)])
    fixed0 = ub[:n_a].reshape(mu, tau) == 0
    fixed1 = ((np.abs(coef - bn) < np.abs(coef - bn - m["gamma"]))
              & ~fixed0 & (m["gamma"] > 0))
    cap = float(np.asarray(node.h)[-1]) if g.shape[0] > 2 * mu else None
    return dict(cap=cap, fixed0=fixed0, fixed1=fixed1,
                d_lb=lb[n_a:n_a + mu], d_ub=ub[n_a:n_a + mu])


def node_rows(st: State) -> list:
    """(tenant, branching state, objective, solution) of each sampled node
    row that converged (an unconverged row goes to the program's host
    re-solve)."""
    out = []
    for k, node, sol, row in st.nodes:
        if bool(np.asarray(sol.converged)[row]):
            out.append((k, node_fixings(st.models[k], node),
                        float(np.asarray(sol.obj)[row]),
                        np.asarray(sol.x)[row]))
    return out


def relative_gap(obj: float, ref: float) -> float:
    """|obj - ref| / |ref|; infinite where either is not a number."""
    if not (np.isfinite(obj) and np.isfinite(ref)):
        return np.inf
    return abs(obj - ref) / max(abs(ref), 1e-300)


def check(st: State, raw: dict, seed: int, log=print) -> dict:
    """Every point of every sweep through the plain evaluator and against
    HiGHS on the relaxation of Eq. 4; a seeded sample of points also
    against HiGHS on Eq. 4 itself.  Every converged row of the window's
    stacked relaxations, and a seeded sample of its converged B&B node
    rows, against HiGHS on the same relaxation; each sampled node row's
    solution also against the rows and bounds of its node."""
    mix = st.mix
    relax_ref = {}

    def relaxation(k, cap):
        if (k, cap) not in relax_ref:
            relax_ref[k, cap] = ref_lp.solve_highs(
                ref_lp.build(st.models[k], cap))[0]
        return relax_ref[k, cap]

    alloc_gap, bound = 0.0, 0.0
    points = [(s["tenant"], p) for s in raw["sweeps"] for p in s["points"]]
    pick = set(data.rng(seed, data.SAMPLE).choice(
        len(points), size=min(int(mix["highs_points"]), len(points)),
        replace=False).tolist())
    for i, (k, p) in enumerate(points):
        m = st.models[k]
        alloc_gap = max(alloc_gap, ref_milp.allocation_gap(
            m, p["cap"], p["alloc"], p["makespan"], p["cost"]))
        lp_bound = relaxation(k, p["cap"])
        inc, lb = np.inf, -np.inf
        if i in pick:
            inc, lb, _ = ref_milp.solve_highs(
                m, p["cap"], float(mix["highs_time_limit_s"]),
                float(mix["gap_tol"]))
            log(f"cap {p['cap']:.6g}: B&B {p['status']} makespan "
                f"{p['makespan']:.9g} bound {p['lb']:.9g}; relaxation "
                f"{lp_bound:.9g}; HiGHS {inc:.9g} bound {lb:.9g}")
        bound = max(bound, ref_milp.bound_excess(lp_bound, p["lb"],
                                                 p["makespan"], inc, lb))
    relax = relaxation_rows(st)
    relax_gap = max((relative_gap(o, relaxation(k, c)) for k, c, o in relax),
                    default=np.inf)
    nodes = node_rows(st)
    node_gap = node_resid = 0.0
    for k, fix, obj, x in nodes:
        lp = ref_lp.build(st.models[k], **fix)
        try:
            ref = ref_lp.solve_highs(lp)[0]
        except RuntimeError:
            ref = np.inf             # the program bounds an empty node
        node_gap = max(node_gap, relative_gap(obj, ref))
        node_resid = max(node_resid, ref_lp.residual(lp, x)
                         if np.isfinite(x).all() else np.inf)
    log(f"compared {len(relax)} relaxation rows ({len(st.relax)} solves) "
        f"and {len(nodes)} of {st.node_rows} node rows")
    n_missing = sum(int(mix["n_points"]) - len(s["points"])
                    for s in raw["sweeps"])
    lim = mix["limits"]
    return {"missing_points": {"value": float(n_missing), "limit": 0.0},
            "alloc_gap": {"value": alloc_gap, "limit": lim["alloc_gap"]},
            "bound_excess": {"value": bound, "limit": float(mix["gap_tol"])},
            "relax_gap": {"value": float(relax_gap),
                          "limit": lim["relax_gap"]},
            "node_gap": {"value": float(node_gap), "limit": lim["node_gap"]},
            "node_resid": {"value": float(node_resid),
                           "limit": lim["node_resid"]}}
