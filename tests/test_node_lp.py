"""The node LP in compact form and the interior point's structured
operator: the border Schur complement against the dense normal
equations, every solve path against the dense path and HiGHS, and a
node of 4,096 tasks on 16 platforms solved with no dense array held."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lp, pareto
from repro.core.problem import AllocationProblem, NodeLP, NodeRows

REPO = Path(__file__).resolve().parents[1]


def _problem(seed: int, mu: int = 4, tau: int = 7) -> AllocationProblem:
    r = np.random.default_rng(seed)
    return AllocationProblem(r.uniform(1e-6, 1e-4, (mu, tau)),
                             r.uniform(0.1, 5.0, (mu, tau)),
                             r.uniform(1e5, 1e7, tau),
                             r.uniform(60, 600, mu),
                             r.uniform(0.01, 0.1, mu))


def _node(p: AllocationProblem, variant: str, cap: bool, seed: int = 3
          ) -> NodeLP:
    """A node LP as the B&B builds it: binaries fixed to 0 and 1, a dead
    platform, branched quanta bounds; with or without a budget."""
    r = np.random.default_rng(seed)
    mu, tau = p.mu, p.tau
    b0 = b1 = d_lb = d_ub = None
    if variant == "fixed":
        b0 = r.random((mu, tau)) < 0.2
        b0[0] = False                      # every task can be placed
        b1 = (r.random((mu, tau)) < 0.2) & ~b0
    elif variant == "dead":
        b0 = np.zeros((mu, tau), bool)
        b0[1] = True
    elif variant == "d_bounds":
        d_lb = np.floor(r.uniform(0, 2, mu))
        d_ub = p.d_max() - r.integers(0, 2, mu)
    budget = 1.5 * float(p.single_platform_cost().min()) if cap else None
    return p.node_lp(budget, b0, b1, d_lb, d_ub)


VARIANTS = [(v, cap) for v in ("root", "fixed", "dead", "d_bounds")
            for cap in (True, False)]


def _std_forms(node: NodeLP):
    dt = jnp.float64
    dense = lp._standardise(*(jnp.asarray(v, dt) for v in (
        node.c, node.a_eq, node.b_eq, node.g, node.h, node.lb, node.ub)))
    rows = NodeRows(*(jnp.asarray(v, dt) for v in node.rows))
    comp = lp._standardise(jnp.asarray(node.c, dt), rows, None, None,
                           *(jnp.asarray(v, dt) for v in (node.h, node.lb,
                                                          node.ub)))
    return dense, comp


def _todense(op: lp.NodeOp):
    """A structured operator's matrix, entry by entry."""
    mu, tau = op.e.shape
    top = np.zeros((tau, mu, tau))
    top[np.arange(tau), :, np.arange(tau)] = np.asarray(op.e).T
    bot = np.asarray(op.p)[:, :, None] * np.asarray(op.w)[None, :, :]
    m_b, n_s = op.bs.shape
    return np.block([[top.reshape(tau, mu * tau), np.zeros((tau, n_s))],
                     [bot.reshape(m_b, mu * tau), np.asarray(op.bs)]])


@pytest.mark.parametrize("variant, cap", VARIANTS)
def test_structured_standard_form_is_the_dense_one(variant, cap):
    dense, comp = _std_forms(_node(_problem(0), variant, cap))
    for f in ("b", "c", "u", "row_scale", "col_scale"):
        np.testing.assert_array_equal(getattr(comp, f), getattr(dense, f))
    np.testing.assert_array_equal(_todense(comp.a), dense.a.a)
    x = jnp.asarray(np.random.default_rng(1).normal(size=dense.a.shape[1]))
    y = jnp.asarray(np.random.default_rng(2).normal(size=dense.a.shape[0]))
    np.testing.assert_allclose(comp.a.matvec(x), dense.a.matvec(x),
                               rtol=1e-13, atol=1e-13)
    np.testing.assert_allclose(comp.a.rmatvec(y), dense.a.rmatvec(y),
                               rtol=1e-13, atol=1e-13)


@pytest.mark.parametrize("variant, cap", VARIANTS)
def test_structured_newton_direction_matches_dense(variant, cap):
    """The border Schur complement gives the dense normal equations'
    direction, at the cold start and at random scalings, on both Newton
    precisions' paths."""
    dense, comp = _std_forms(_node(_problem(1), variant, cap))
    m, n = dense.a.shape
    init, _, _ = lp._ipm_ops(dense.a, dense.b, dense.c, dense.u,
                             jnp.asarray(1e-9), "xla")
    c0 = init(True)
    r = np.random.default_rng(4)
    scalings = [c0.x / c0.z, jnp.asarray(10.0 ** r.uniform(-3, 3, n))]
    rhs = jnp.asarray(r.normal(size=m))
    with jax.default_matmul_precision("highest"):
        for d in scalings:
            for ridge in (0.0, 1e-11):
                dy_d, _ = dense.a.normal_solve(d, rhs, "xla", "float64",
                                               ridge)
                dy_c, _ = comp.a.normal_solve(d, rhs, "xla", "float64",
                                              ridge)
                rel = float(jnp.linalg.norm(dy_c - dy_d)
                            / jnp.linalg.norm(dy_d))
                assert rel <= 1e-10, (rel, ridge)


@pytest.mark.parametrize("variant, cap", VARIANTS)
def test_structured_dense_and_highs_objectives_agree(variant, cap):
    node = _node(_problem(2), variant, cap)
    with jax.default_matmul_precision("highest"):
        comp = lp.solve_node_lp(node)
        dense = lp.solve_lp(node.c, node.a_eq, node.b_eq, node.g, node.h,
                            node.lb, node.ub)
    a_eq, g = node.sparse_rows()
    ref = lp.scipy_reference_lp(node.c, a_eq, node.b_eq, g, node.h,
                                node.lb, node.ub)
    assert ref.status == 0 and bool(comp.converged)
    for obj in (float(comp.obj), float(dense.obj)):
        assert abs(obj - ref.fun) <= 1e-8 * abs(ref.fun)


def _rows_by_loop(p: AllocationProblem, node: NodeLP):
    """The node's dense rows built row by row from the problem, as the
    solver's dense node LP was built: the reference for the compact
    form's views."""
    mu, tau = p.mu, p.tau
    n_a, n = mu * tau, mu * tau + mu + 1
    a_eq = np.zeros((tau, n))
    for j in range(tau):
        a_eq[j, j:n_a:tau] = 1.0
    rows = []
    for kind in ("latency", "quanta"):
        for i in range(mu):
            row = np.zeros(n)
            row[i * tau:(i + 1) * tau] = node.coef[i]
            if kind == "latency":
                row[n - 1] = -1.0
            else:
                row[n_a + i] = -p.rho[i]
            rows.append(row)
    if node.cap is not None:
        row = np.zeros(n)
        row[n_a:n_a + mu] = p.pi
        rows.append(row)
    return a_eq, np.stack(rows)


@pytest.mark.parametrize("variant, cap", VARIANTS)
def test_sparse_rows_are_the_dense_rows(variant, cap):
    p = _problem(3)
    node = _node(p, variant, cap)
    a_ref, g_ref = _rows_by_loop(p, node)
    a_eq, g = node.sparse_rows()
    np.testing.assert_array_equal(a_eq.toarray(), a_ref)
    np.testing.assert_array_equal(g.toarray(), g_ref)
    np.testing.assert_array_equal(node.a_eq, a_ref)
    np.testing.assert_array_equal(node.g, g_ref)
    np.testing.assert_array_equal(node.b_eq, np.ones(p.tau))
    assert node.c[-1] == 1.0 and not node.c[:-1].any()
    want_h = np.concatenate([-node.const, -node.const]
                            + ([[node.cap]] if cap else []))
    np.testing.assert_array_equal(node.h, want_h)


@pytest.mark.parametrize("compact", [False, True])
def test_stacked_node_solves_match_the_dense_stacked_solve(compact):
    p = _problem(4)
    nodes = [_node(p, v, True, seed=k) for k, v in
             enumerate(("root", "fixed", "dead", "d_bounds"))]
    dense = [np.stack([getattr(nd, f) for nd in nodes])
             for f in ("c", "a_eq", "b_eq", "g", "h", "lb", "ub")]
    with jax.default_matmul_precision("highest"):
        a = lp.solve_node_lps_stacked(nodes, compact=compact)
        b = lp.solve_lp_stacked(*dense, compact=compact)
    assert np.asarray(a.converged).all()
    np.testing.assert_allclose(a.obj, b.obj, rtol=1e-8)


def test_float32_newton_path_agrees_on_node_lps():
    p = _problem(5)
    nodes = [_node(p, v, True, seed=k) for k, v in
             enumerate(("root", "fixed", "dead", "d_bounds"))]
    with jax.default_matmul_precision("highest"):
        f64 = lp.solve_node_lps_stacked(nodes)
        f32 = lp.solve_node_lps_stacked(nodes, newton_dtype="float32")
    assert np.asarray(f32.converged).all()
    np.testing.assert_allclose(f32.obj, f64.obj, rtol=1e-8)


def test_relaxation_frontier_copies_the_node_rows_once():
    """The stacked relaxation batches only the budget: one node's fields
    and eight rhs rows are copied; its answers are the node solves'."""
    from repro import obs
    p = _problem(6)
    caps = np.linspace(1.0, 3.0, 8) * float(p.single_platform_cost().min())
    with obs.scope() as scoped:
        _, lbs = pareto.relaxation_frontier(p, caps)
    got = scoped["counters"]
    node = p.node_lp(float(caps[0]))
    once = sum(np.size(getattr(node, f)) for f in
               ("c", "coef", "rho", "pi", "lb", "ub"))
    assert got["lp.put_rows"] == 8
    assert got["lp.put_bytes"] == 8 * (once + 8 * node.h.size)
    solo = lp.solve_node_lps_stacked(pareto.frontier_nodes(p, caps))
    np.testing.assert_allclose(lbs, solo.obj, rtol=1e-9)


def _dense_call(c, rows, h, lb, ub):
    """The dense arrays of a node-form stacked call: the same LPs."""
    coef, rho, pi = (np.asarray(v) for v in rows)
    h = np.asarray(h)
    cap = 0.0 if h.shape[-1] > 2 * coef.shape[-2] else None

    def one(cf, r, pv):
        a_eq, g = NodeLP(cf, np.zeros(r.shape), r, pv, cap, None,
                         None).sparse_rows()
        return a_eq.toarray(), g.toarray()

    if coef.ndim == 2:
        a_eq, g = one(coef, rho, pi)
    else:
        pairs = [one(coef[k], rho[k], pi[k]) for k in range(coef.shape[0])]
        a_eq = np.stack([x for x, _ in pairs])
        g = np.stack([y for _, y in pairs])
    return c, a_eq, np.ones(a_eq.shape[:-1]), g, h, lb, ub


def test_batched_tradeoff_is_the_dense_paths():
    """``milp_tradeoff_batched`` on the benchmark's tiny configuration
    gives the points it gives when every node LP is solved densely."""
    sys.path.insert(0, str(REPO))
    from bench import data
    from tests.bench.tinybench import tiny_config
    m = data.tenant_models(tiny_config(), 2**31 + 12345)[0]
    p = AllocationProblem(m["beta"], m["gamma"], m["n"], m["rho"], m["pi"])
    kw = dict(n_points=3, node_limit=20, time_limit_s=np.inf, gap_tol=1e-4)
    structured = pareto.milp_tradeoff_batched(p, **kw)
    stacked = lp.solve_lp_stacked

    def dense(c, a_eq, b_eq, g, h, lb, ub, **k):
        if isinstance(a_eq, lp.NodeRows):
            return stacked(*_dense_call(c, a_eq, h, lb, ub), **k)
        return stacked(c, a_eq, b_eq, g, h, lb, ub, **k)

    lp.solve_lp_stacked = dense
    try:
        reference = pareto.milp_tradeoff_batched(p, **kw)
    finally:
        lp.solve_lp_stacked = stacked
    assert len(structured.points) == len(reference.points)
    for a, b in zip(structured.points, reference.points):
        assert a.cost_cap == b.cost_cap
        assert abs(a.makespan - b.makespan) <= 1e-9 * b.makespan
        assert abs(a.cost - b.cost) <= 1e-9 * max(b.cost, 1.0)


@pytest.mark.parametrize("seed", range(4))
def test_an_infeasible_node_stops_when_its_newton_step_breaks_down(seed):
    """A node whose budget no allocation meets diverges until its Newton
    system gives no finite step; the row stops there, unconverged, long
    before the iteration cap, and HiGHS finds the node infeasible."""
    p = _problem(seed)
    node = p.node_lp(0.2 * float(p.single_platform_cost().min()))
    sol = lp.solve_node_lps_stacked([node, node], tol=1e-7)
    assert not np.asarray(sol.converged).any()
    assert np.asarray(sol.iters).max() < lp._MAX_ITERS // 2
    a_eq, g = node.sparse_rows()
    ref = lp.scipy_reference_lp(node.c, a_eq, node.b_eq, g, node.h,
                                node.lb, node.ub)
    assert ref.status == 2


def test_node_objectives_hold_the_tolerance_as_the_book_grows():
    """At 512 tasks on 16 platforms the mean complementarity under 1e-7
    would leave node objectives ~5e-7 off HiGHS; the duality gap relative
    to the objective, which bounds that error, must pass the tolerance
    too."""
    sys.path.insert(0, str(REPO))
    import json
    from bench import data
    cfg = json.loads((REPO / "bench/configs/portfolio4096x16.json")
                     .read_text())
    cfg["n_tasks"] = 512
    m = data.tenant_models(cfg, 2147512002)[1]
    p = AllocationProblem(m["beta"], m["gamma"], m["n"], m["rho"], m["pi"])
    cost = p.single_platform_cost()
    nodes = pareto.frontier_nodes(
        p, np.geomspace(1.01 * float(cost.min()), float(cost.max()), 8))
    sol = lp.solve_node_lps_stacked(nodes, tol=1e-7)
    assert np.asarray(sol.converged).all()
    for k, node in enumerate(nodes):
        a_eq, g = node.sparse_rows()
        ref = lp.scipy_reference_lp(node.c, a_eq, node.b_eq, g, node.h,
                                    node.lb, node.ub)
        assert abs(float(sol.obj[k]) - ref.fun) <= 2e-7 * abs(ref.fun)


def test_a_row_cut_short_converges_only_with_its_objective():
    """A row can stop short of its tolerance (the iteration cap, a
    rejected Newton step).  At 2,048 tasks on 16 platforms the mean
    complementarity passes 1e-6 while the objective is still ~9e-6 off
    HiGHS; the reported ``gap`` also holds the duality gap relative to
    the objective, so every iterate that would count as converged has
    its objective within 2e-6."""
    sys.path.insert(0, str(REPO))
    import json
    from bench import data
    cfg = json.loads((REPO / "bench/configs/portfolio4096x16.json")
                     .read_text())
    cfg["n_tasks"] = 2048
    m = data.tenant_models(cfg, 2147512002)[1]
    p = AllocationProblem(m["beta"], m["gamma"], m["n"], m["rho"], m["pi"])
    node = pareto.frontier_nodes(
        p, [2.0 * float(p.single_platform_cost().min())])[0]
    a_eq, g = node.sparse_rows()
    ref = lp.scipy_reference_lp(node.c, a_eq, node.b_eq, g, node.h,
                                node.lb, node.ub).fun
    dt = jnp.float64
    std = lp._standardise(*lp._on_device(
        (node.c, node.rows, None, None, node.h, node.lb, node.ub), dt))
    init, make_body, report = lp._ipm_ops(std.a, std.b, std.c, std.u,
                                          jnp.asarray(0.0, dt), "xla")
    body, report = jax.jit(make_body("float64")), jax.jit(report)
    bounded = std.u < lp._INF_UB * 0.5
    carry, judged, mu_only = init(True), 0, 0
    for _ in range(80):
        carry = body(carry)
        rp, rd, gap = (float(v) for v in report(carry))
        x, z = carry.x, carry.z
        mu = float((x @ z + jnp.where(bounded, carry.s * carry.w, 0).sum())
                   / (x.size + bounded.sum()))
        err = abs(float(std.c @ x + node.c @ node.lb) - ref) / abs(ref)
        if rp < 1e-6 and rd < 1e-6:
            mu_only += mu < 1e-6 and err > 2e-6
            if gap < 1e-6:
                judged += 1
                assert err <= 2e-6, (gap, err)
        if mu < 1e-12:
            break
    assert judged and mu_only


@pytest.mark.parametrize("driver", ["single", "stacked", "compact", "dense"])
def test_a_pinned_share_comes_back_at_its_bound(driver):
    """A share fixed to 0 keeps a sliver of interior inside the solver;
    the solution returns it at exactly 0, so it weighs in none of its
    platform's rows."""
    p = _problem(6)
    node = _node(p, "fixed", True)
    pinned = node.ub == node.lb
    assert pinned.any()
    with jax.default_matmul_precision("highest"):
        if driver == "single":
            x = lp.solve_node_lp(node).x
        elif driver == "dense":
            x = lp.solve_lp(node.c, node.a_eq, node.b_eq, node.g, node.h,
                            node.lb, node.ub).x
        else:
            x = lp.solve_node_lps_stacked([node, node],
                                          compact=driver == "compact").x[1]
    x = np.asarray(x)
    np.testing.assert_array_equal(x[pinned], node.lb[pinned])
    assert (x[~pinned] <= node.ub[~pinned]).all()


def test_a_4096_task_node_solves_with_no_dense_array():
    """tau=4,096 on mu=16: the node holds ~1.6 MB, a width-2 stacked
    solve runs through the border, and no array it leaves behind holds
    more than 8 MB (the dense equality rows alone would be 2.15 GB)."""
    p = _problem(7, mu=16, tau=4096)
    node = p.node_lp(2.0 * float(p.single_platform_cost().min()))
    held = sum(v.nbytes for v in node if isinstance(v, np.ndarray))
    assert held < 8e6
    before = {id(a) for a in jax.live_arrays()}
    sol = lp.solve_node_lps_stacked([node, node], tol=1e-7)
    assert np.asarray(sol.converged).all()
    alloc = p.split_node_x(np.asarray(sol.x[0]))[0]
    np.testing.assert_allclose(alloc.sum(axis=0), 1.0, atol=1e-6)
    new = [a for a in jax.live_arrays() if id(a) not in before]
    assert new and max(a.nbytes for a in new) <= 8e6


def test_sharded_node_solve_agrees_on_a_forced_8_device_mesh():
    """The structured operator shards by rows like the dense arrays: on
    a forced 8-device CPU mesh, monolithic and compacted node solves
    agree with the unsharded ones."""
    code = textwrap.dedent("""
        import numpy as np
        from repro.core import lp, pareto
        from repro.launch.mesh import make_solver_mesh
        from tests.test_node_lp import _problem
        p = _problem(8)
        caps = np.linspace(1.0, 3.0, 12) * float(
            p.single_platform_cost().min())
        nodes = pareto.frontier_nodes(p, caps)
        mesh = make_solver_mesh()
        assert lp.mesh_n_shards(mesh) == 8
        gaps = []
        for compact in (False, True):
            a = lp.solve_node_lps_stacked(nodes, compact=compact)
            b = lp.solve_node_lps_stacked(nodes, compact=compact, mesh=mesh)
            assert np.asarray(b.converged).all()
            gaps.append(float(np.max(np.abs(np.asarray(a.obj)
                                             - np.asarray(b.obj)))))
        print("GAPS", max(gaps))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.pathsep.join([str(REPO / "src"), str(REPO)]))
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    gap = float(proc.stdout.split("GAPS")[-1])
    assert gap <= 1e-8
