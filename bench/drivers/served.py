"""Open-loop replans against the allocation server.

Each request is one tenant's replan after a market event: one budget cap
on one of the deployment's fitted tenant problems, half of them with a
dead mask of preempted platforms.  Requests are due on a Poisson-like
schedule at the mix's fixed rate and are sent when due, whatever the
server's backlog; a request's latency runs from its due time to the
moment its frontier resolves.

So that a seed changes the inputs and not the amount of work, every seed
gets the same multiset of inter-arrival gaps (exponential quantiles at
the rate), budget positions and dead-mask sizes, each in its own
seeded order; the seed draws the tenants' fitted models, which platforms
are dead, and the orders.
"""
from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np

from bench import data
from bench.reference import lp as ref_lp


@dataclasses.dataclass
class State:
    models: list
    schedule: list
    limits: dict
    problems: list = None
    server: object = None
    requests: list = None


def schedule(models: list, mix: dict, seed: int, seconds: float,
             rate: float = None) -> list:
    """The requests due in a window: dicts of ``due`` (s from the window's
    start), ``tenant``, ``cap`` and ``dead`` ((mu,) bool or None)."""
    rate = float(mix["rate_per_s"] if rate is None else rate)
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    r = data.rng(seed, data.REQUESTS)
    gaps = r.permutation(-np.log1p(-q) / rate)
    gaps *= seconds * n / (n + 1) / gaps.sum()
    due = np.cumsum(gaps)
    budget_at = r.permutation(q)
    tenants = r.permutation(np.arange(n) % len(models))
    n_dead = r.permutation([1 + k % 3 if k < n * mix["dead_share"] else 0
                            for k in range(n)])
    mu = models[0]["beta"].shape[0]
    out = []
    for k in range(n):
        dead = None
        if n_dead[k]:
            dead = np.zeros(mu, bool)
            dead[r.choice(mu, size=int(n_dead[k]), replace=False)] = True
        m = models[int(tenants[k])]
        lo, hi = data.budget_range(m, dead)
        out.append(dict(due=float(due[k]), tenant=int(tenants[k]),
                        cap=lo + float(budget_at[k]) * (hi - lo), dead=dead))
    return out


def requests_for(problems: list, plan: list) -> list:
    from repro.serving import AllocRequest
    return [AllocRequest(f"tenant{q['tenant']}", problems[q["tenant"]],
                         np.array([q["cap"]]), dead=q["dead"]) for q in plan]


def setup(cfg: dict, mix: dict, seed: int, seconds: float, *, root=None,
          log=print) -> State:
    from repro.core.problem import AllocationProblem
    from repro.serving import AllocationServer
    models = data.tenant_models(cfg, seed)
    st = State(models, schedule(models, mix, seed, seconds), mix["limits"])
    st.problems = [AllocationProblem(m["beta"], m["gamma"], m["n"], m["rho"],
                                     m["pi"], m["names"]) for m in models]
    st.requests = requests_for(st.problems, st.schedule)
    st.server = AllocationServer(ladder_max=int(mix["ladder_max"]))
    t0 = time.perf_counter()
    widths = st.server.warmup(st.problems[0])
    warm_live_rows(st, int(mix["ladder_max"]))
    log(f"warmed ladder widths {widths} and every live-row count in "
        f"{time.perf_counter() - t0:.3f} s; {len(st.requests)} requests "
        f"due in the window")
    return st


def warm_live_rows(st: State, ladder_max: int) -> None:
    """A dispatch slices its solution to its live rows and reads their
    convergence on the device, which compiles small programs for every
    (ladder width, live rows) pair: run that path once per live-row count
    with every row retired (no interior-point iterations)."""
    from repro.core import lp, pareto
    lo, _ = data.budget_range(st.models[0])
    node = pareto.frontier_nodes(st.problems[0], [lo])[0]
    for k in range(1, ladder_max + 1):
        sol = lp.solve_node_lps_ladder([node] * k, ladder_max=ladder_max,
                                       row_active=np.zeros(k, bool))
        pareto.tenant_frontiers([st.problems[0]] * k,
                                [np.array([lo])] * k, sol)


def window(st: State, seconds: float, requests=None, plan=None) -> dict:
    """Send every request at its due time, wait for all of them, and
    return the raw samples."""
    requests = st.requests if requests is None else requests
    plan = st.schedule if plan is None else plan
    srv = st.server
    n = len(requests)
    done_at = np.full(n, np.nan)
    sent_at = np.full(n, np.nan)
    futs = [None] * n
    all_done = threading.Event()
    remaining = [n]
    lock = threading.Lock()

    def on_done(k):
        def cb(_):
            done_at[k] = time.perf_counter()
            with lock:
                remaining[0] -= 1
                if remaining[0] == 0:
                    all_done.set()
        return cb

    srv.start()
    try:
        t0 = time.perf_counter()
        for k, (req, q) in enumerate(zip(requests, plan)):
            wait = t0 + q["due"] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            sent_at[k] = time.perf_counter()
            futs[k] = srv.submit(req)
            futs[k].add_done_callback(on_done(k))
        t_sent = time.perf_counter()
        all_done.wait(timeout=max(120.0, 2 * seconds))
        t_end = time.perf_counter()
    finally:
        srv.stop(drain=False)
    results = []
    for f in futs:
        ok = f.done() and f.exception() is None
        results.append(f.result() if ok else None)
    due_abs = t0 + np.array([q["due"] for q in plan])
    lat = np.where(np.isnan(done_at), t_end, done_at) - due_abs
    failed = sum(r is None for r in results)
    return dict(attempted=n, failed=failed, latency_s=lat,
                late_s=sent_at - due_abs, results=results,
                dispatches=list(srv.dispatches),
                t0=t0, t_sent=t_sent, t_end=t_end)


def end_to_end(raw: dict) -> dict:
    lat = raw["latency_s"]
    return {"frontier_p50_s": float(np.percentile(lat, 50)),
            "frontier_p95_s": float(np.percentile(lat, 95))}


def release(st: State) -> None:
    st.server = st.problems = st.requests = None


def check(st: State, raw: dict, seed: int, log=print) -> dict:
    """Every answered request against HiGHS on the plain relaxation:
    the widest relative gap of the served makespan, and the widest
    residual of the served allocation at it."""
    lim = st.limits
    gap = resid = 0.0
    for q, res in zip(st.schedule, raw["results"]):
        if res is None:
            continue
        m = st.models[q["tenant"]]
        ref, _ = ref_lp.solve_highs(ref_lp.build(m, q["cap"], q["dead"]))
        mk = float(res.frontier.makespans[0])
        gap = max(gap, abs(mk - ref) / ref)
        resid = max(resid, ref_lp.allocation_residual(
            m, q["cap"], q["dead"], res.frontier.allocs[0], mk))
    log(f"checked {raw['attempted'] - raw['failed']} answers against HiGHS")
    return {"missing_answers": {"value": float(raw["failed"]), "limit": 0.0},
            "frontier_gap": {"value": gap, "limit": lim["frontier_gap"]},
            "alloc_resid": {"value": resid, "limit": lim["alloc_resid"]}}
