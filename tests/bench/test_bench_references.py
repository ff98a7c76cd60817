"""The plain references at tiny size against the program they judge, and
the lower-precision control against the limits the cells hold: the
references agree with a sound program, and the control does not pass."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from bench import data, episodes
from bench.reference import episodes as ref_ep
from bench.reference import lp as ref_lp
from bench.reference import milp as ref_milp

from tinybench import tiny_config, tiny_spot_config

REPO = Path(__file__).resolve().parents[2]


def _limits(traffic):
    return json.loads(
        (REPO / f"bench/traffic/{traffic}.json").read_text())["limits"]


@pytest.fixture(scope="module")
def model():
    return data.tenant_models(tiny_config(), 2**31 + 99)[0]


def _problem(m):
    from repro.core.problem import AllocationProblem
    return AllocationProblem(m["beta"], m["gamma"], m["n"], m["rho"],
                             m["pi"])


def _cases(m):
    dead = np.zeros(m["beta"].shape[0], bool)
    dead[1] = True
    out = []
    for d in (None, dead):
        lo, hi = data.budget_range(m, d)
        out += [(lo + u * (hi - lo), d) for u in (0.0, 0.3, 1.0)]
    return out


def test_lp_reference_matches_the_program(model):
    from repro.core import lp, pareto
    for cap, dead in _cases(model):
        ref, x = ref_lp.solve_highs(ref_lp.build(model, cap, dead))
        sol = lp.solve_node_lps_stacked(
            pareto.frontier_nodes(_problem(model), [cap], dead))
        assert abs(float(sol.obj[0]) - ref) / ref < 1e-7
        mu, tau = model["beta"].shape
        assert ref_lp.allocation_residual(
            model, cap, dead, x[:mu * tau].reshape(mu, tau), ref) < 1e-9


def test_plain_ipm_matches_highs_in_float64(model):
    for cap, dead in _cases(model):
        lp = ref_lp.build(model, cap, dead)
        ref, _ = ref_lp.solve_highs(lp)
        obj, _ = ref_lp.solve_ipm(lp, np.float64)
        assert abs(obj - ref) / ref < 1e-8


def test_float32_control_fails_the_served_limits(model):
    lim = _limits("replans_poisson")
    worst = 0.0
    for cap, dead in _cases(model):
        lp = ref_lp.build(model, cap, dead)
        ref, _ = ref_lp.solve_highs(lp)
        obj, x = ref_lp.solve_ipm(lp, np.float32)
        mu, tau = model["beta"].shape
        worst = max(worst, abs(obj - ref) / ref / lim["frontier_gap"],
                    ref_lp.allocation_residual(
                        model, cap, dead, x[:mu * tau].reshape(mu, tau), obj)
                    / lim["alloc_resid"])
    assert worst > 1.0


def test_evaluator_matches_the_program(model):
    from repro.core import heuristics
    r = np.random.default_rng(0)
    p = _problem(model)
    for _ in range(20):
        a = r.random(model["beta"].shape) * (r.random(model["beta"].shape)
                                             > 0.4)
        a[0] += 1e-3
        a /= a.sum(axis=0)
        assert ref_milp.evaluate(model, a) == pytest.approx(
            heuristics.evaluate(p, a), rel=1e-14)


def test_milp_reference_matches_program_highs(model):
    from repro.core import milp
    lo, hi = data.budget_range(model)
    for cap in (lo, 0.5 * (lo + hi)):
        inc, lb, alloc = ref_milp.solve_highs(model, cap, 20.0, 1e-6)
        res = milp.solve(_problem(model), cap, backend="highs")
        assert inc == pytest.approx(res.makespan, rel=1e-5)
        assert lb <= inc * (1 + 1e-9)
        mk, cost = ref_milp.evaluate(model, alloc)
        assert mk <= inc * (1 + 1e-7) and cost <= cap * (1 + 1e-9)


def test_float32_control_fails_the_milp_limit(model):
    lim = _limits("milp_sweep")["alloc_gap"]
    lo, hi = data.budget_range(model)
    cap = 0.5 * (lo + hi)
    _, _, alloc = ref_milp.solve_highs(model, cap, 20.0, 1e-6)
    a32 = alloc.astype(np.float32)
    mk32, cost32 = ref_milp.evaluate(model, a32, np.float32)
    assert ref_milp.allocation_gap(model, cap, alloc, *ref_milp.evaluate(
        model, alloc)) <= lim
    assert ref_milp.allocation_gap(model, cap, a32, mk32, cost32) > lim


def _branched_nodes(m):
    """Node LPs as the program builds them at B&B nodes: binaries fixed
    to 0 and to 1, quanta bounded, with and without a budget."""
    p = _problem(m)
    mu, tau = m["beta"].shape
    r = np.random.default_rng(7)
    lo, hi = data.budget_range(m)
    out = []
    for cap in (None, hi, 0.5 * (lo + hi)):
        for _ in range(3):
            b0 = r.random((mu, tau)) < 0.15
            b0[0] = False                       # every task can be placed
            b1 = (r.random((mu, tau)) < 0.15) & ~b0
            out.append(p.node_lp(cap, b0, b1, d_lb=np.zeros(mu),
                                 d_ub=p.d_max() + r.integers(0, 2, mu)))
    return out


def test_node_reference_matches_the_program(model):
    from bench.drivers import milp as driver
    from repro.core import lp
    lim = _limits("milp_sweep")
    nodes = _branched_nodes(model)
    for batch in (nodes[:3], nodes[3:]):        # one shape per batch
        sol = lp.solve_node_lps_stacked(batch, tol=1e-7)
        for k, node in enumerate(batch):
            ref_node = ref_lp.build(model, **driver.node_fixings(model, node))
            ref, _ = ref_lp.solve_highs(ref_node)
            assert bool(sol.converged[k])
            assert driver.relative_gap(float(sol.obj[k]), ref) < (
                lim["node_gap"] / 10)
            assert ref_lp.residual(ref_node, np.asarray(sol.x[k])) < (
                lim["node_resid"] / 10)
            # a sibling's answer breaks this node's rows or bounds
            other = np.asarray(sol.x[(k + 1) % len(batch)])
            assert ref_lp.residual(ref_node, other) > lim["node_resid"]


def test_float32_control_fails_the_relaxation_and_node_limits(model):
    from bench.drivers import milp as driver
    lim = _limits("milp_sweep")
    lo, hi = data.budget_range(model)
    relax = max(driver.relative_gap(ref_lp.solve_ipm(lp, np.float32)[0],
                                    ref_lp.solve_highs(lp)[0])
                for lp in (ref_lp.build(model, c) for c in (lo, hi)))
    node = resid = 0.0
    for n in _branched_nodes(model):
        lp = ref_lp.build(model, **driver.node_fixings(model, n))
        obj, x = ref_lp.solve_ipm(lp, np.float32)
        node = max(node, driver.relative_gap(obj, ref_lp.solve_highs(lp)[0]))
        resid = max(resid, ref_lp.residual(lp, x)
                    if np.isfinite(x).all() else np.inf)
    assert relax > lim["relax_gap"] and node > lim["node_gap"]
    assert resid > lim["node_resid"]


def _market(seed, n=6):
    cfg = tiny_spot_config()
    m = data.tenant_models(tiny_config(), seed)[0]
    cat = dict(beta=m["beta"], gamma=m["gamma"], rho=m["rho"], pi=m["pi"],
               n=m["n"], names=m["names"])
    eps = [episodes.generate(cat["names"], cfg,
                             data.rng(seed, data.EPISODES, i))
           for i in range(n)]
    return cfg, cat, eps


def test_episode_reference_matches_the_fused_replay():
    from repro.core.problem import AllocationProblem
    from repro.market import events as ev
    from repro.market import fused, simulator
    cfg, cat, eps = _market(5)
    problem = AllocationProblem(cat["beta"], cat["gamma"], cat["n"],
                                cat["rho"], cat["pi"], cat["names"])
    mine, slos, alloc0s, refs = [], [], [], []
    for i, e in enumerate(eps):
        occ, kind, evs = episodes.slot_events(e)
        lat = (cat["beta"] * cat["n"] + cat["gamma"]).sum(axis=1)[kind]
        slo = 0.8 * float(lat[occ].min())
        a0 = ref_ep.initial_split(cat, occ, kind)
        slos.append(slo)
        alloc0s.append(a0)
        refs.append(ref_ep.replay(cat, occ, kind, evs, e["horizon_s"], slo,
                                  a0, 9))
        mine.append(ev.MarketEpisode(
            i, e["horizon_s"], cat["names"], e["max_platforms"],
            tuple(e["initial"]),
            tuple(ev.MarketEvent(t, k, n, tuple(p.items()))
                  for t, k, n, p in e["events"])))
    out = fused.run_episodes_vmapped(
        simulator.catalog_from_problem(problem), problem.n, mine,
        policy_kind="resplit", slo_latencies=slos, alloc0s=alloc0s,
        tensors=ev.stack_event_tensors(mine))
    for got, ref, e in zip(out, refs, eps):
        totals = {f: getattr(got, f) for f in ref}
        assert ref_ep.totals_gap(totals, ref, e["horizon_s"]) < 1e-12


def test_float32_control_fails_the_regret_limit():
    lim = _limits("regret_resplit")["episode_gap"]
    _, cat, eps = _market(6, n=4)
    worst = 0.0
    for e in eps:
        occ, kind, evs = episodes.slot_events(e)
        lat = (cat["beta"] * cat["n"] + cat["gamma"]).sum(axis=1)[kind]
        slo = 0.8 * float(lat[occ].min())
        a0 = ref_ep.initial_split(cat, occ, kind)
        ref = ref_ep.replay(cat, occ, kind, evs, e["horizon_s"], slo, a0, 9)
        low = ref_ep.replay(cat, occ, kind, evs, e["horizon_s"], slo, a0, 9,
                            np.float32)
        worst = max(worst, ref_ep.totals_gap(low, ref, e["horizon_s"]))
    assert worst > lim
