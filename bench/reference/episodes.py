"""Plain reference for a regret sweep: one spot-market episode replayed
by a Python loop under the re-split policy, every quantity computed in a
chosen float precision.

Semantics.  The fleet is a row of slots, each empty or holding an
instance of a catalogue kind with its own throughput, price and
contention scales.  A dead (empty) slot keeps kind 0's model scaled by
``DEAD_PENALTY``, so no plan uses it.  Over each interval between events
the standing allocation runs at its makespan and bills its cost per
makespan; the interval counts as an SLO violation when the makespan
exceeds the SLO (by more than ``SLO_RTOL``).  After every event the
policy re-plans: the latency-proportional split over live slots and the
scalarised sweep at ``n_weights`` cost weights (each projected onto the
live slots), keeping the cheapest plan within the SLO, or the fastest
when none is.  The fleet starts on the latency-proportional split of its
initial instances, which counts as the first replan.
"""
from __future__ import annotations

import numpy as np

DEAD_PENALTY = 1e6
SLO_RTOL = 1e-9
SHARE_EPS = 1e-12
QUANTUM_EPS = 1e-12


def problem(cat: dict, occ, kind, bsc, psc, csc, dtype):
    """(beta*n, gamma, rho, pi) of the fleet's slots."""
    scale = np.where(occ, 1.0, DEAD_PENALTY).astype(dtype)
    beta = cat["beta"][kind].astype(dtype) * (bsc * csc * scale)[:, None]
    bn = beta * cat["n"].astype(dtype)[None, :]
    gamma = cat["gamma"][kind].astype(dtype) * scale[:, None]
    return bn, gamma, cat["rho"][kind].astype(dtype), \
        cat["pi"][kind].astype(dtype) * psc


def evaluate(bn, gamma, rho, pi, alloc):
    g_l = (bn * alloc + gamma * (alloc > SHARE_EPS)).sum(axis=1)
    cost = (np.ceil(g_l / rho - alloc.dtype.type(QUANTUM_EPS)) * pi).sum()
    return g_l.max(), cost


def _split(weights, tau):
    w = np.maximum(weights, 0)
    return np.tile((w / w.sum())[:, None], (1, tau))


def _cheapest(cost_1p, tau, dtype):
    a = np.zeros((cost_1p.size, tau), dtype)
    a[int(np.argmin(cost_1p))] = 1
    return a


def _project(bn, gamma, alloc, alive):
    a = np.where(alive[:, None], np.maximum(alloc, 0), 0)
    empty = a.sum(axis=0) <= 1e-9
    if empty.any():
        w = np.where(alive, 1 / (bn + gamma).sum(axis=1), 0)
        a[:, empty] = (w / w.sum())[:, None]
    return a / a.sum(axis=0)[None, :]


def plan(bn, gamma, rho, pi, alive, slo, n_weights: int):
    """The re-split policy's plan for one fleet state."""
    dtype = bn.dtype.type
    tau = bn.shape[1]
    lat = (bn + gamma).sum(axis=1)
    cost_1p = np.ceil(lat / rho) * pi
    cands = [_split(np.where(alive, 1 / lat, 0), tau)]
    for lam in np.linspace(0.0, 1.0, n_weights):
        if lam >= 1.0:
            a = _cheapest(cost_1p, tau, dtype)
        else:
            score = (1 - dtype(lam)) * lat / lat.max() \
                + dtype(lam) * cost_1p / cost_1p.max()
            w = 1 / np.maximum(score, dtype(1e-12))
            w = np.where(score <= np.quantile(score, max(0.05, 1 - lam)),
                         w, 0)
            a = _split(w, tau) if w.sum() > 0 else _cheapest(cost_1p, tau,
                                                              dtype)
        cands.append(_project(bn, gamma, a, alive))
    best, best_key, fast, fast_mk = None, None, None, np.inf
    for a in cands:
        mk, cost = evaluate(bn, gamma, rho, pi, a)
        if mk < fast_mk:
            fast, fast_mk = a, mk
        if mk <= slo * (1 + SLO_RTOL) and (best_key is None
                                             or (cost, mk) < best_key):
            best, best_key = a, (cost, mk)
    return best if best is not None else fast


def initial_split(cat: dict, occ, kind, dtype=np.float64):
    """The allocation a fleet starts an episode on: every task split over
    the live slots in proportion to their single-platform speed."""
    one = np.ones(occ.size, dtype)
    bn, gamma, _, _ = problem(cat, occ, kind, one, one, one, dtype)
    return _split(np.where(occ, 1 / (bn + gamma).sum(axis=1), 0),
                  bn.shape[1])


def replay(cat: dict, occ0, kind0, events, horizon: float, slo: float,
           alloc0, n_weights: int, dtype=np.float64) -> dict:
    """Episode totals: accrued cost, time-weighted makespan, SLO-violation
    seconds and intervals, replans.  ``events`` are ``(time, kind, slot,
    payload)`` with slots resolved; ``alloc0`` is the t=0 plan."""
    occ = np.array(occ0, bool)
    kind = np.array(kind0, np.int64)
    s = occ.size
    bsc, psc, csc = (np.ones(s, dtype) for _ in range(3))
    alloc = np.asarray(alloc0).astype(dtype)
    slo = dtype(slo)
    cost_acc = mk_dt = viol_s = dtype(0)
    viol_n, replans = 0, 1
    t_prev = dtype(0)

    def close(dt):
        nonlocal cost_acc, mk_dt, viol_s, viol_n
        if dt <= 0:
            return
        mk, cost = evaluate(*problem(cat, occ, kind, bsc, psc, csc, dtype),
                            alloc)
        cost_acc += cost / mk * dt
        mk_dt += mk * dt
        if mk > slo * (1 + SLO_RTOL):
            viol_s += dt
            viol_n += 1

    for t, what, i, payload in events:
        t = dtype(t)
        close(max(t - t_prev, dtype(0)))
        if what in ("arrival", "departure"):
            occ[i] = what == "arrival"
            kind[i] = payload["kind_index"] if what == "arrival" else 0
            bsc[i] = psc[i] = csc[i] = 1
        elif what in ("price_tick", "price_shock"):
            psc[i] = payload["price_scale"]
        elif what in ("degrade", "recover"):
            bsc[i] = payload["beta_scale"]
        else:
            csc[i] = payload["throughput_scale"]
        prob = problem(cat, occ, kind, bsc, psc, csc, dtype)
        alloc = plan(*prob, occ, slo, n_weights)
        replans += 1
        t_prev = max(t, t_prev)
    close(max(dtype(horizon) - t_prev, dtype(0)))
    return dict(accrued_cost=float(cost_acc),
                avg_makespan=float(mk_dt / max(dtype(horizon),
                                               dtype(1e-12))),
                slo_violation_s=float(viol_s), slo_violations=viol_n,
                replans=replans)


def totals_gap(got: dict, ref: dict, horizon: float) -> float:
    """Widest gap between two episodes' totals: relative for cost and
    makespan, as a share of the horizon for violation seconds, and the
    absolute difference of the violation and replan counts."""
    return float(max(
        abs(got["accrued_cost"] - ref["accrued_cost"])
        / max(abs(ref["accrued_cost"]), 1e-300),
        abs(got["avg_makespan"] - ref["avg_makespan"])
        / max(abs(ref["avg_makespan"]), 1e-300),
        abs(got["slo_violation_s"] - ref["slo_violation_s"]) / horizon,
        abs(got["slo_violations"] - ref["slo_violations"]),
        abs(got["replans"] - ref["replans"])))
