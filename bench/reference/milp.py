"""Plain reference for the exact trade-off: an evaluator of allocations
under the paper's latency and billing models, and HiGHS on Eq. 4 as
written (A real, B binary, D integer, F real), built from a
deployment's arrays.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.optimize import Bounds, LinearConstraint, milp

# a share counts as placed (its setup is paid) above this; a platform's
# busy time within this of a whole number of quanta bills that number
SHARE_EPS = 1e-12
QUANTUM_EPS = 1e-12


def evaluate(m: dict, alloc, dtype=np.float64):
    """(makespan, billed cost) of an allocation, computed in ``dtype``."""
    a = np.asarray(alloc).astype(dtype)
    bn = (m["beta"] * m["n"][None, :]).astype(dtype)
    g_l = (bn * a + m["gamma"].astype(dtype) * (a > SHARE_EPS)).sum(axis=1)
    cost = (np.ceil(g_l / m["rho"].astype(dtype) - dtype(QUANTUM_EPS))
            * m["pi"].astype(dtype)).sum()
    return float(g_l.max()), float(cost)


def allocation_gap(m: dict, cap: float, alloc, makespan, cost) -> float:
    """Widest disagreement, relative, between a reported point and its
    allocation: every task fully placed, no negative share, the reported
    makespan and cost equal to the evaluated ones, the cost within the
    budget."""
    a = np.asarray(alloc, np.float64)
    mk, c = evaluate(m, a)
    return float(max(np.abs(a.sum(axis=0) - 1.0).max(),
                     max(0.0, -float(a.min())),
                     abs(makespan - mk) / mk,
                     abs(cost - c) / max(c, 1e-300),
                     max(0.0, (c - cap) / cap)))


def solve_highs(m: dict, cap: float, time_limit_s: float,
                gap_tol: float):
    """(incumbent makespan or inf, lower bound, incumbent allocation or
    None) of Eq. 4 at one budget by HiGHS within a time limit."""
    beta, gamma, n = m["beta"], m["gamma"], m["n"]
    mu, tau = beta.shape
    n_a = mu * tau
    bn = beta * n[None, :]
    ib, idd, iff = n_a, 2 * n_a, 2 * n_a + mu
    n_x = iff + 1
    c = np.zeros(n_x)
    c[iff] = 1.0
    integrality = np.zeros(n_x)
    integrality[ib:iff] = 1
    lat = sp.kron(sp.eye(mu), np.ones((1, tau)))
    place = sp.hstack([sp.kron(np.ones((1, mu)), sp.eye(tau)),
                       sp.csr_matrix((tau, n_a + mu + 1))])
    mk = sp.hstack([lat @ sp.diags(bn.ravel()), lat @ sp.diags(gamma.ravel()),
                    sp.csr_matrix((mu, mu)), -np.ones((mu, 1))])
    setup = sp.hstack([sp.eye(n_a), -sp.eye(n_a), sp.csr_matrix((n_a, mu + 1))])
    quanta = sp.hstack([sp.diags(1 / m["rho"]) @ lat @ sp.diags(bn.ravel()),
                        sp.diags(1 / m["rho"]) @ lat
                        @ sp.diags(gamma.ravel()),
                        -sp.eye(mu), sp.csr_matrix((mu, 1))])
    budget = sp.hstack([sp.csr_matrix((1, 2 * n_a)),
                        sp.csr_matrix(m["pi"][None, :]),
                        sp.csr_matrix((1, 1))])
    rows = sp.vstack([mk, setup, quanta, budget]).tocsr()
    hi = np.concatenate([np.zeros(mu + n_a + mu), [float(cap)]])
    lat_1p = (bn + gamma).sum(axis=1)
    ub = np.full(n_x, np.inf)
    ub[:idd] = 1.0
    ub[idd:iff] = np.ceil(lat_1p.max() / m["rho"]) + 1.0
    res = milp(c, integrality=integrality, bounds=Bounds(np.zeros(n_x), ub),
               constraints=[LinearConstraint(place, 1.0, 1.0),
                            LinearConstraint(rows, -np.inf, hi)],
               options=dict(time_limit=float(time_limit_s),
                            mip_rel_gap=float(gap_tol)))
    inc = float(res.fun) if res.x is not None else np.inf
    alloc = None if res.x is None else res.x[:n_a].reshape(mu, tau)
    bound = getattr(res, "mip_dual_bound", None)
    return inc, (-np.inf if bound is None or not np.isfinite(bound)
                 else float(bound)), alloc


def bound_excess(lp_bound: float, lb: float, makespan: float,
                 highs_inc: float, highs_lb: float) -> float:
    """Widest relative excess of the bound relations a B&B point must
    keep: its lower bound at least the root relaxation, at most its own
    incumbent, at most HiGHS's incumbent; HiGHS's bound at most the
    point's incumbent."""
    parts = [(lp_bound - lb) / lp_bound, (lb - makespan) / makespan]
    if np.isfinite(highs_inc):
        parts.append((lb - highs_inc) / highs_inc)
    if np.isfinite(highs_lb):
        parts.append((highs_lb - makespan) / makespan)
    return float(max(0.0, *parts))
