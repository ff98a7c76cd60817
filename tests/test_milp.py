"""Structure-exploiting B&B vs the HiGHS oracle on Eq. 4."""
import numpy as np
import pytest

from repro.core import heuristics, lp, milp
from repro.core.problem import AllocationProblem


def random_problem(seed, mu=4, tau=6):
    rng = np.random.default_rng(seed)
    beta = rng.uniform(1e-6, 2e-5, (mu, tau))
    gamma = rng.uniform(0.5, 30.0, (mu, tau))
    n = rng.uniform(1e6, 5e7, tau)
    rho = rng.choice([60.0, 300.0, 600.0, 3600.0], mu)
    pi_hour = rng.uniform(0.2, 1.0, mu)
    pi = pi_hour * rho / 3600.0
    return AllocationProblem(beta, gamma, n, rho, pi)


@pytest.mark.parametrize("seed", range(5))
def test_bnb_matches_highs_unconstrained(seed):
    p = random_problem(seed)
    r_b = milp.solve_bnb(p, None, node_limit=800, time_limit_s=60)
    r_h = milp.solve_highs(p, None)
    assert r_b.alloc is not None and r_h.alloc is not None
    # both report TRUE-model makespans; B&B must be within 2% of HiGHS
    assert r_b.makespan <= r_h.makespan * 1.02 + 1e-9, (
        r_b.makespan, r_h.makespan)


@pytest.mark.parametrize("seed", range(3))
def test_bnb_respects_budget(seed):
    p = random_problem(seed + 10)
    c_l = p.single_platform_cost().min()
    cap = float(c_l * 1.5)
    r = milp.solve_bnb(p, cap, node_limit=500, time_limit_s=60)
    assert r.alloc is not None
    assert r.cost <= cap * (1 + 1e-6)
    np.testing.assert_allclose(r.alloc.sum(axis=0), 1.0, atol=1e-6)


def test_infeasible_budget():
    p = random_problem(2)
    cap = float(p.single_platform_cost().min()) * 0.01
    r = milp.solve_bnb(p, cap, node_limit=100, time_limit_s=30)
    assert r.alloc is None
    r_h = milp.solve_highs(p, cap)
    assert r_h.alloc is None


def test_lower_bound_sound():
    p = random_problem(7)
    r = milp.solve_bnb(p, None, node_limit=800, time_limit_s=60)
    assert r.lower_bound <= r.makespan * (1 + 1e-6)


def test_budget_monotonicity():
    """More budget can only reduce the optimal makespan."""
    p = random_problem(11)
    c_l = float(p.single_platform_cost().min())
    r_top = milp.solve_bnb(p, None, node_limit=400, time_limit_s=60)
    caps = np.linspace(c_l, max(r_top.cost, c_l) * 1.2, 4)
    prev = np.inf
    for ck in caps[::-1]:        # decreasing budget
        r = milp.solve_bnb(p, float(ck), node_limit=400, time_limit_s=60)
        if r.alloc is None:
            continue
        assert r.makespan >= prev - 1e-6 or np.isinf(prev) \
            or r.makespan <= prev * 1.05   # anytime slack
        prev = min(prev, r.makespan)


def test_milp_beats_or_ties_heuristic():
    """The paper's headline claim, on random instances."""
    for seed in range(4):
        p = random_problem(seed + 20, mu=5, tau=8)
        top = milp.solve_bnb(p, None, node_limit=600, time_limit_s=60)
        c_u = top.cost
        for frac in (1.0, 0.6):
            cap = float(p.single_platform_cost().min()) * (1 - frac) \
                + c_u * frac
            r = milp.solve_bnb(p, cap, node_limit=600, time_limit_s=60)
            h = heuristics.best_heuristic_for_budget(p, cap)
            if r.alloc is None:
                continue
            h_mk = (np.inf if h is None
                    else heuristics.evaluate(p, h)[0])
            assert r.makespan <= h_mk * 1.01 + 1e-9


# ---------------------------------------------------------------------------
# Warm starts and the lockstep batched sweep
# ---------------------------------------------------------------------------

def test_warm_start_does_not_change_answer():
    p = random_problem(30)
    cap = float(p.single_platform_cost().min() * 2)
    cold = milp.solve_bnb(p, cap, node_limit=400, time_limit_s=60)
    assert cold.alloc is not None
    warm = milp.solve_bnb(p, cap, node_limit=400, time_limit_s=60,
                          warm_alloc=cold.alloc,
                          lower_bound0=cold.lower_bound)
    assert warm.alloc is not None
    assert warm.makespan <= cold.makespan * (1 + 1e-6)
    assert warm.cost <= cap * (1 + 1e-6)


def test_warm_start_with_tight_bound_closes_at_root():
    p = random_problem(31)
    cap = float(p.single_platform_cost().min() * 2)
    cold = milp.solve_bnb(p, cap, node_limit=400, time_limit_s=60)
    assert cold.alloc is not None
    warm = milp.solve_bnb(p, cap, node_limit=400, time_limit_s=60,
                          warm_alloc=cold.alloc,
                          lower_bound0=cold.makespan * (1 - 1e-6))
    assert warm.nodes == 0
    assert warm.status == "optimal"
    assert warm.makespan <= cold.makespan * (1 + 1e-6)


def test_warm_start_over_budget_is_repaired():
    p = random_problem(32)
    cap = float(p.single_platform_cost().min() * 1.2)
    expensive = milp.solve_bnb(p, None, node_limit=200, time_limit_s=30)
    r = milp.solve_bnb(p, cap, node_limit=200, time_limit_s=30,
                       warm_alloc=expensive.alloc)
    if r.alloc is not None:
        assert r.cost <= cap * (1 + 1e-6)


@pytest.mark.parametrize("seed", range(3))
def test_sweep_matches_serial_bnb(seed):
    """Lockstep batched sweep vs one serial B&B per cap.

    In exact mode (batch_width=1, reference lp_tol) the sweep explores
    the same tree as the serial solver and must agree tightly; in the
    default wide/loose mode truncated search may order-diverge by a small
    amount (and is often better — incumbents propagate)."""
    p = random_problem(seed + 40)
    c_l = float(p.single_platform_cost().min())
    caps = np.linspace(c_l, c_l * 3, 4)
    kw = dict(node_limit=150, time_limit_s=30)
    exact = milp.solve_bnb_sweep(p, caps, batch_width=1, lp_tol=1e-9, **kw)
    fast = milp.solve_bnb_sweep(p, caps, **kw)
    assert len(exact) == len(fast) == len(caps)
    for ck, re_, rf in zip(caps, exact, fast):
        rs = milp.solve_bnb(p, float(ck), **kw)
        if rs.alloc is None:
            assert re_.alloc is None or re_.cost <= ck * (1 + 1e-6)
            continue
        assert re_.alloc is not None and rf.alloc is not None
        assert re_.makespan <= rs.makespan * (1 + 1e-3) + 1e-9
        assert rf.makespan <= rs.makespan * 1.02 + 1e-9
        for rb in (re_, rf):
            assert rb.cost <= ck * (1 + 1e-6)
            np.testing.assert_allclose(rb.alloc.sum(axis=0), 1.0,
                                       atol=1e-6)


def test_sweep_unconstrained_matches_serial():
    p = random_problem(45)
    rs = milp.solve_bnb(p, None, node_limit=300, time_limit_s=30)
    rb = milp.solve_bnb_sweep(p, [None], node_limit=300, time_limit_s=30,
                              batch_width=1, lp_tol=1e-9)[0]
    assert rb.alloc is not None
    assert rb.makespan <= rs.makespan * (1 + 1e-3) + 1e-9
    # default wide/loose mode: small order-divergence allowed
    rw = milp.solve_bnb_sweep(p, [None], node_limit=300,
                              time_limit_s=30)[0]
    assert rw.alloc is not None
    assert rw.makespan <= rs.makespan * 1.02 + 1e-9


def test_sweep_rejects_mixed_caps():
    p = random_problem(46)
    with pytest.raises(ValueError):
        milp.solve_bnb_sweep(p, [None, 10.0])


def test_sweep_priority_refill_results_unchanged():
    """Wide batches (batch_width > n_trees) refill best-bound across
    trees and process solved rows in best-bound order (in-round
    incumbent propagation).  Results must stay within solver tolerance
    of the serial per-cap B&B — the reordering changes only WHEN bounds
    become available, never what they prove."""
    p = random_problem(50)
    c_l = float(p.single_platform_cost().min())
    caps = np.linspace(c_l, c_l * 3, 3)
    kw = dict(node_limit=150, time_limit_s=30)
    for width in (8, 16):                    # both > n_trees = 3
        wide = milp.solve_bnb_sweep(p, caps, batch_width=width, **kw)
        assert len(wide) == len(caps)
        for ck, rw in zip(caps, wide):
            rs = milp.solve_bnb(p, float(ck), **kw)
            if rs.alloc is None:
                continue
            assert rw.alloc is not None
            assert rw.makespan <= rs.makespan * 1.02 + 1e-9
            assert rw.cost <= ck * (1 + 1e-6)
            np.testing.assert_allclose(rw.alloc.sum(axis=0), 1.0,
                                       atol=1e-6)


def test_sweep_early_exit_bit_matches():
    """Per-row early exit must not change WHAT the sweep computes: every
    budget point's allocation, objectives and node count bit-match the
    non-early-exit path (padding rows were always discarded; active rows
    of a vmapped solve are independent of their batch-mates)."""
    from repro.core import lp
    p = random_problem(40)
    c_l = float(p.single_platform_cost().min())
    caps = np.linspace(c_l, c_l * 3, 4)
    kw = dict(node_limit=150, time_limit_s=30)
    on = milp.solve_bnb_sweep(p, caps, early_exit=True, **kw)
    n_compiled = lp.stacked_compile_count()
    off = milp.solve_bnb_sweep(p, caps, early_exit=False, **kw)
    for a, b in zip(on, off):
        if a.alloc is None:
            assert b.alloc is None
            continue
        np.testing.assert_array_equal(a.alloc, b.alloc)
        assert a.makespan == b.makespan
        assert a.cost == b.cost
        assert a.nodes == b.nodes
    # the row_active mask is traced: rows retiring mid-sweep (and turning
    # the mask off entirely) must never trigger a recompile
    assert lp.stacked_compile_count() == n_compiled


def test_sweep_early_exit_matches_serial_and_saves_rows():
    """Early-exit sweep vs one serial B&B per cap: identical answers
    (within solver tolerance), strictly fewer Newton rows than lockstep
    accounting."""
    from repro.core import lp
    p = random_problem(41)
    c_l = float(p.single_platform_cost().min())
    caps = np.linspace(c_l, c_l * 3, 3)
    kw = dict(node_limit=150, time_limit_s=30)
    lp.reset_newton_row_stats()
    sweep = milp.solve_bnb_sweep(p, caps, **kw)
    stats = lp.newton_row_stats()
    assert stats["calls"] >= 1
    assert stats["active_rows"] < stats["lockstep_rows"]
    for ck, rb in zip(caps, sweep):
        rs = milp.solve_bnb(p, float(ck), **kw)
        if rs.alloc is None:
            continue
        assert rb.alloc is not None
        assert rb.makespan <= rs.makespan * 1.02 + 1e-9
        assert rb.cost <= ck * (1 + 1e-6)


def test_sweep_compact_matches_monolithic():
    """``compact=True`` routes every lockstep round's stacked solve
    through the chunked mid-call-compaction driver: per-budget frontier
    results match the monolithic sweep to solver tolerance, repeat
    compacted sweeps are deterministic, and nothing recompiles once the
    width ladder is warm."""
    from repro.core import lp
    p = random_problem(43)
    c_l = float(p.single_platform_cost().min())
    caps = np.linspace(c_l, c_l * 3, 3)
    kw = dict(node_limit=100, time_limit_s=30)
    plain = milp.solve_bnb_sweep(p, caps, **kw)
    comp = milp.solve_bnb_sweep(p, caps, compact=True, **kw)
    count = lp.stacked_compile_count()
    for a, b in zip(plain, comp):
        if a.alloc is None:
            assert b.alloc is None
            continue
        assert abs(a.makespan - b.makespan) <= 1e-6 * a.makespan + 1e-9
        assert abs(a.cost - b.cost) <= 1e-6 * a.cost + 1e-9
    comp2 = milp.solve_bnb_sweep(p, caps, compact=True, **kw)
    assert lp.stacked_compile_count() == count
    for b, b2 in zip(comp, comp2):
        assert b.makespan == b2.makespan
        assert b.nodes == b2.nodes


def test_sweep_linsolve_backends_agree():
    """The whole lockstep sweep through the Pallas batched-Cholesky
    backend lands on the same frontier as the xla backend.  Both sweeps
    must PROVE optimality: a sweep cut by a node or time limit keeps an
    exploration-order-dependent incumbent, so only finished trees are
    comparable, and no wall clock may decide where they stop."""
    p = random_problem(42, mu=3, tau=4)
    c_l = float(p.single_platform_cost().min())
    caps = np.linspace(c_l, c_l * 3, 3)
    gap_tol = 1e-4
    kw = dict(node_limit=2000, time_limit_s=np.inf, gap_tol=gap_tol)
    base = milp.solve_bnb_sweep(p, caps, linsolve="xla", **kw)
    pall = milp.solve_bnb_sweep(p, caps, linsolve="pallas", **kw)
    for ck, a, b in zip(caps, base, pall):
        assert a.status == b.status == "optimal", (a.status, b.status)
        assert a.alloc is not None and b.alloc is not None
        # each incumbent is within gap_tol of the common optimum
        assert abs(a.makespan - b.makespan) <= gap_tol * a.makespan + 1e-9
        assert a.cost <= ck * (1 + 1e-6) and b.cost <= ck * (1 + 1e-6)


def test_pinned_root_excludes_platforms():
    """A root pin (dead platform / empty fleet slot) must keep every
    incumbent and node solve off the pinned rows, and match the solve of
    the problem with those platforms removed.  Both trees must finish:
    incumbents of trees cut by a node limit depend on exploration order,
    which any change to the node LP numerics reshuffles."""
    p = random_problem(51, mu=4, tau=4)
    from repro.core.problem import AllocationProblem
    pin = np.zeros((4, 4), dtype=bool)
    pin[1, :] = True
    keep = [0, 2, 3]
    sub = AllocationProblem(p.beta[keep], p.gamma[keep], p.n,
                            p.rho[keep], p.pi[keep])
    kw = dict(node_limit=2000, time_limit_s=np.inf)
    for cap in (None, float(p.single_platform_cost().min() * 2)):
        r_pin = milp.solve_bnb(p, cap, pinned=pin, **kw)
        r_sub = milp.solve_bnb(sub, cap, **kw)
        assert r_pin.status == r_sub.status == "optimal"
        assert r_pin.alloc is not None and r_sub.alloc is not None
        assert r_pin.alloc[1].sum() == 0.0
        assert abs(r_pin.makespan - r_sub.makespan) \
            <= 1e-3 * r_sub.makespan + 1e-9


def test_pinned_cheapest_platform_with_tight_budget_is_infeasible():
    """Budget-repair fallbacks must respect the pin: when the globally
    cheapest platform is pinned (dead) and the budget only IT could
    satisfy, the solve must report infeasible instead of silently
    allocating to the dead platform."""
    p = random_problem(51, mu=4, tau=6)
    cost = p.single_platform_cost()
    cheapest = int(np.argmin(cost))
    pin = np.zeros((4, 6), dtype=bool)
    pin[cheapest, :] = True
    # affordable for the pinned platform only
    cap = float(cost[cheapest]) * 1.01
    if float(np.sort(cost)[1]) <= cap:
        pytest.skip("second-cheapest platform also fits this budget")
    r = milp.solve_bnb(p, cap, pinned=pin, node_limit=200, time_limit_s=30)
    assert r.alloc is None, "allocated to a pinned (dead) platform"
    caps = [cap, cap * 1.02]
    for rs in milp.solve_bnb_sweep(p, caps, pinned=pin, node_limit=200,
                                   time_limit_s=30):
        assert rs.alloc is None or rs.alloc[cheapest].sum() == 0.0


def test_degenerate_warm_alloc_is_projected():
    """A warm start with unassigned task columns must not poison the
    incumbent (evaluate() silently under-counts unassigned tasks)."""
    p = random_problem(33)
    bad = np.zeros((p.mu, p.tau))
    bad[0, 0] = 1.0                       # every other task unassigned
    r = milp.solve_bnb(p, None, node_limit=100, time_limit_s=30,
                       warm_alloc=bad)
    assert r.alloc is not None
    np.testing.assert_allclose(r.alloc.sum(axis=0), 1.0, atol=1e-6)
    mk, _ = heuristics.evaluate(p, r.alloc)
    assert abs(mk - r.makespan) <= 1e-6 * max(mk, 1.0)
    ref = milp.solve_bnb(p, None, node_limit=100, time_limit_s=30)
    assert r.makespan >= ref.makespan * (1 - 1e-3)


# ---------------------------------------------------------------------------
# Dispatch rungs: a half-live round runs at the lower rung
# ---------------------------------------------------------------------------

def _traced_sweep(p, caps, **kw):
    """One sweep with spans on: its results, spans and counter deltas."""
    from repro import obs
    obs.enable()
    try:
        with obs.scope() as scoped:
            res = milp.solve_bnb_sweep(p, caps, **kw)
    finally:
        obs.disable()
    events = sorted(obs.trace_events(), key=lambda e: e.ts_ns)
    obs.clear_trace()
    return res, events, scoped["counters"]


@pytest.mark.parametrize("batch_width, rungs", [(8, [8, 4]), (5, [5, 4])])
def test_sweep_dispatches_half_live_rounds_at_the_lower_rung(batch_width,
                                                             rungs):
    """A round whose popped nodes fit the lower rung is solved at that
    width; ``milp.round``'s ``width`` and ``milp.batch_rows`` still read
    the sweep's batch width, ``dispatch_width`` and
    ``milp.dispatch_rows`` the width paid."""
    assert milp._dispatch_rungs(batch_width) == rungs
    p = random_problem(40)
    c_l = float(p.single_platform_cost().min())
    caps = np.linspace(c_l, c_l * 3, 4)
    kw = dict(node_limit=6, time_limit_s=np.inf, batch_width=batch_width)
    milp.solve_bnb_sweep(p, caps, **kw)                 # compile
    _, events, counters = _traced_sweep(p, caps, **kw)
    rounds = [e for e in events if e.name == "milp.round"]
    solves = [e for e in events if e.name == "lp.solve_stacked"]
    assert len(rounds) == len(solves) == counters["milp.rounds"]
    for r, s in zip(rounds, solves):
        want = rungs[1] if r.attrs["popped"] <= rungs[1] else rungs[0]
        assert r.attrs["width"] == batch_width
        assert r.attrs["dispatch_width"] == s.attrs["width"] == want
    assert any(r.attrs["dispatch_width"] == rungs[1] for r in rounds)
    assert counters["milp.batch_rows"] == batch_width * len(rounds)
    assert counters["milp.dispatch_rows"] == sum(
        r.attrs["dispatch_width"] for r in rounds)
    assert (counters["milp.nodes"] <= counters["milp.dispatch_rows"]
            < counters["milp.batch_rows"])


def test_sweep_rungs_do_not_change_the_search(monkeypatch):
    """Dispatching at the lower rung changes only the padding: the same
    nodes are popped and every budget point ends with the same
    allocation, makespan, cost, bound, status and node count as at the
    full width alone."""
    p = random_problem(41)
    c_l = float(p.single_platform_cost().min())
    caps = np.linspace(c_l, c_l * 3, 4)
    kw = dict(node_limit=20, time_limit_s=np.inf)
    laddered = milp.solve_bnb_sweep(p, caps, **kw)
    monkeypatch.setattr(milp, "_dispatch_rungs", lambda width: [width])
    full = milp.solve_bnb_sweep(p, caps, **kw)
    for a, b in zip(laddered, full):
        assert a.status == b.status and a.nodes == b.nodes
        if a.alloc is None:
            assert b.alloc is None
            continue
        np.testing.assert_array_equal(a.alloc, b.alloc)
        assert a.makespan == b.makespan
        assert a.cost == b.cost
        assert a.lower_bound == b.lower_bound


def test_sweep_of_a_known_shape_compiles_nothing():
    """The first sweep of a node-LP shape compiles both rungs, even one
    whose rounds use only the lower rung (a node limit of 1 leaves one
    round of 4 roots); a sweep of another problem of that shape that
    uses both rungs then compiles nothing."""
    from repro.core import lp
    count = None
    for seed, node_limit in ((52, 1), (53, 6)):
        p = random_problem(seed, mu=3, tau=8)
        c_l = float(p.single_platform_cost().min())
        caps = np.linspace(c_l, c_l * 3, 4)
        _, events, _ = _traced_sweep(p, caps, node_limit=node_limit,
                                     time_limit_s=np.inf)
        count = lp.stacked_compile_count() if count is None else count
    assert lp.stacked_compile_count() == count
    assert {e.attrs["dispatch_width"] for e in events
            if e.name == "milp.round"} == {8, 4}
    assert not any(e.name == "lp.warm_width" for e in events)


def test_width_one_sweep_compiles_one_width():
    """At batch width 1 there is one rung: the sweep compiles one
    program for its node-LP shape and warms no other width."""
    from repro.core import lp
    p = random_problem(54, mu=2, tau=9)
    c_l = float(p.single_platform_cost().min())
    caps = np.linspace(c_l, c_l * 3, 3)
    assert milp._dispatch_rungs(1) == [1]
    count = lp.stacked_compile_count()
    _, events, _ = _traced_sweep(p, caps, node_limit=4, time_limit_s=np.inf,
                                 batch_width=1)
    assert lp.stacked_compile_count() == count + 1
    assert {e.attrs["width"] for e in events
            if e.name == "lp.solve_stacked"} == {1}
    assert not any(e.name == "lp.warm_width" for e in events)


# ---------------------------------------------------------------------------
# Nodes integral within _FRAC_TOL, the host re-solve
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [2147484425, 2147507104])
def test_node_with_near_zero_shares_is_branched_not_closed(seed):
    """A relaxation whose shares all lie within _FRAC_TOL of 0 or 1 can
    still charge a share under 1e-6 only gamma times the share, while its
    rounded candidate pays the whole setup: such a node is branched, and
    both B&B engines reach HiGHS's optimum on the benchmark's tiny
    configuration (seeds where they used to stop above it)."""
    import sys
    from pathlib import Path
    root = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root))
    from bench import data
    from tests.bench.tinybench import tiny_config
    m = data.tenant_models(tiny_config(), seed)[0]
    p = AllocationProblem(m["beta"], m["gamma"], m["n"], m["rho"], m["pi"])
    cap, gap_tol = 0.658, 1e-4
    ref = milp.solve_highs(p, cap)
    kw = dict(node_limit=5000, time_limit_s=np.inf, gap_tol=gap_tol)
    for res in (milp.solve_bnb(p, cap, **kw),
                milp.solve_bnb_sweep(p, [cap], **kw)[0]):
        assert res.status == "optimal"
        assert res.makespan <= ref.makespan * (1 + gap_tol)


def test_host_resolve_builds_sparse_rows(monkeypatch):
    """The HiGHS fallback of a node solve takes the node's sparse rows
    and never builds the dense ones."""
    from repro.core.problem import NodeLP
    p = random_problem(12, mu=3, tau=5)
    node = p.node_lp(float(p.single_platform_cost().min() * 2))
    dense = lp.scipy_reference_lp(node.c, node.a_eq, node.b_eq, node.g,
                                  node.h, node.lb, node.ub)

    def refuse(self):
        raise AssertionError("dense node rows built")

    monkeypatch.setattr(NodeLP, "a_eq", property(refuse))
    monkeypatch.setattr(NodeLP, "g", property(refuse))
    x, obj, status = milp._solve_node(node, prefer_jax=False)
    assert status == "ok"
    assert abs(obj - dense.fun) <= 1e-9 * abs(dense.fun)
    np.testing.assert_allclose(x, dense.x, atol=1e-9)


def test_host_resolve_counts_each_node_and_tells_infeasible_apart():
    """A node the stacked solve leaves unconverged goes to HiGHS on its
    sparse rows: each re-solve counts once in ``milp.host_resolves``, an
    empty node reads infeasible, and a feasible one matches the dense
    rows' answer."""
    from repro import obs
    p = random_problem(13, mu=3, tau=6)
    c_l = float(p.single_platform_cost().min())
    nodes = [p.node_lp(c_l * f) for f in (0.5, 1.2, 2.0, 3.0, 0.2)]
    with obs.scope() as scoped:
        got = [milp._solve_node(n, prefer_jax=False) for n in nodes]
    assert scoped["counters"]["milp.host_resolves"] == len(nodes)
    assert {st for _, _, st in got} == {"ok", "infeasible"}
    for n, (x, obj, st) in zip(nodes, got):
        if st == "ok":
            dense = lp.scipy_reference_lp(n.c, n.a_eq, n.b_eq, n.g, n.h,
                                          n.lb, n.ub)
            assert abs(obj - dense.fun) <= 1e-9 * abs(dense.fun)
