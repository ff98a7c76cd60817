"""Trade-off generation: epsilon-constraint MILP frontier vs heuristic."""
import numpy as np

from repro.core import pareto
from tests.test_milp import random_problem


def test_cost_bounds_ordering():
    p = random_problem(1)
    c_l, c_u, top = pareto.cost_bounds(p, backend="bnb", node_limit=300,
                                       time_limit_s=30)
    assert c_l <= c_u + 1e-9
    assert top.alloc is not None


def test_milp_frontier_dominates_heuristic():
    """Paper Fig. 3: the ILP trade-off curve is never above the heuristic
    curve (hypervolume at least as large)."""
    p = random_problem(5, mu=4, tau=6)
    t_ilp = pareto.milp_tradeoff(p, n_points=5, backend="bnb",
                                 node_limit=300, time_limit_s=30)
    t_heur = pareto.heuristic_tradeoff(p, n_points=5)
    c_i, l_i = t_ilp.as_arrays()
    c_h, l_h = t_heur.as_arrays()
    ref_c = max(c_i.max(), c_h.max()) * 1.1
    ref_l = max(l_i.max(), l_h.max()) * 1.1
    hv_i = pareto.hypervolume(c_i, l_i, ref_c, ref_l)
    hv_h = pareto.hypervolume(c_h, l_h, ref_c, ref_l)
    assert hv_i >= hv_h * 0.999


def test_frontier_monotone_after_filter():
    p = random_problem(9)
    t = pareto.milp_tradeoff(p, n_points=5, backend="bnb", node_limit=300,
                             time_limit_s=30)
    c, l = t.as_arrays()
    mask = pareto.pareto_filter(c, l)
    cs, ls = c[mask], l[mask]
    order = np.argsort(cs)
    assert (np.diff(ls[order]) <= 1e-9).all()


def test_hypervolume_simple():
    hv = pareto.hypervolume(np.array([1.0]), np.array([1.0]), 2.0, 2.0)
    assert abs(hv - 1.0) < 1e-12
    hv2 = pareto.hypervolume(np.array([1.0, 1.5]), np.array([1.0, 0.5]),
                             2.0, 2.0)
    assert abs(hv2 - 1.25) < 1e-12


def test_batched_tradeoff_matches_serial():
    """The batched engine must agree with the serial sweep within solver
    tolerance at every budget point (and is allowed to be better, since
    incumbents propagate across the sweep).  Every tree must finish:
    incumbents of trees cut by a node limit depend on exploration order,
    which any change to the node LP numerics reshuffles."""
    p = random_problem(7, mu=3, tau=4)
    kw = dict(node_limit=3000, time_limit_s=np.inf)
    t_ser = pareto.milp_tradeoff(p, n_points=6, backend="bnb", **kw)
    t_bat = pareto.milp_tradeoff_batched(p, n_points=6, **kw)
    assert all(pt.meta["status"] == "optimal"
               for t in (t_ser, t_bat) for pt in t.points)
    # pair sweep points by grid position; the two caps grids come from
    # independently computed anchors, so match with isclose, not float==
    ser = sorted((pt.cost_cap, pt.makespan) for pt in t_ser.points
                 if pt.cost_cap is not None)
    bat = sorted((pt.cost_cap, pt.makespan) for pt in t_bat.points
                 if pt.cost_cap is not None)
    pairs = [(cs, ms, mb) for (cs, ms), (cb, mb) in zip(ser, bat)
             if np.isclose(cs, cb, rtol=1e-3)]
    assert len(pairs) >= 4
    # per matched cap: batched never worse than serial beyond solver
    # tolerance (it may be better — incumbents propagate across the sweep)
    for c, ms, mb in pairs:
        assert mb <= ms * (1 + 1e-3) + 1e-9, (c, mb, ms)
    # and never below the LP relaxation bound at the same budget
    caps = np.linspace(t_bat.c_lower, max(t_bat.c_upper, t_bat.c_lower), 6)
    _, lbs = pareto.relaxation_frontier(p, caps)
    for pt in t_bat.points:
        if pt.cost_cap is None:
            continue
        k = int(np.argmin(np.abs(caps - pt.cost_cap)))
        assert pt.makespan >= lbs[k] * (1 - 1e-6)


def test_batched_tradeoff_points_respect_budget():
    p = random_problem(11, mu=4, tau=6)
    t = pareto.milp_tradeoff_batched(p, n_points=5, node_limit=150,
                                     time_limit_s=30)
    for pt in t.points:
        if pt.cost_cap is not None:
            assert pt.cost <= pt.cost_cap * (1 + 1e-6)
        np.testing.assert_allclose(pt.alloc.sum(axis=0), 1.0, atol=1e-6)


def test_relaxation_frontier_lower_bounds_milp():
    """vmapped LP-relaxation frontier: monotone in budget and <= the true
    MILP makespan at every cap."""
    import numpy as np
    from repro.core import milp

    p = random_problem(13)
    c_l, c_u, _ = pareto.cost_bounds(p, backend="bnb", node_limit=200,
                                     time_limit_s=30)
    caps = np.linspace(max(c_l, 1e-6), max(c_u, c_l) * 1.2, 5)
    caps_out, lbs = pareto.relaxation_frontier(p, caps)
    # more budget -> lower (or equal) relaxed makespan
    assert (np.diff(lbs) <= 1e-6).all()
    for ck, lb in zip(caps, lbs):
        r = milp.solve(p, cost_cap=float(ck), backend="bnb",
                       node_limit=200, time_limit_s=30)
        if r.alloc is not None:
            assert lb <= r.makespan * (1 + 1e-6)
