"""Whole-horizon DP oracle: the minimum-cost trajectory for a KNOWN trace.

The per-interval clairvoyant (:class:`repro.market.policies.OraclePolicy`)
re-solves each inter-event interval greedily, which leaves two gaps: its
pick minimises lexicographic ``(cost, makespan)`` among SLO-feasible
candidates rather than the objective episodes are actually billed on
(``cost/makespan`` dollars per second plus the SLA charge), and a policy
outside its finite candidate set can beat it — producing *negative*
"regret".  This module closes both gaps with a whole-horizon dynamic
program over the materialised event trace:

* the interval grid comes from replaying the episode's shadow fleet, so
  state ``i`` is exactly the (occupancy, degradation, price, contention)
  the simulator would expose at interval ``i``;
* the move set per interval is the same one online policies draw from —
  the scalarised heuristic battery, the latency-proportional split, the
  cheapest single platform, and an ``n_caps``-point budget-grid of node
  LP relaxations (dead slots pinned) — plus "hold" chains that carry
  each t=0 plan forward under the static policy's strand-projection
  rule, plus any realised policy trajectories passed in via ``paths``;
* ALL node LPs across every (interval, budget) pair are solved in ONE
  :func:`repro.core.lp.solve_node_lps_ladder` call — the DP itself is a
  megabatch workload, and ``mesh=`` shards its row axis over a device
  mesh exactly like any other stacked solve;
* backward induction over (interval, column) with an optional
  ``switch_cost`` charge per plan change then yields the cheapest
  achievable trajectory.  With the simulator's free replans
  (``switch_cost=0``, the default) this is the per-interval lower
  envelope of the move set — including every realised path fed in, so
  ``policy_total_cost - oracle_total_cost >= 0`` holds BY CONSTRUCTION
  for any policy whose run was passed via ``paths`` (a policy's total
  cost is exactly the sum of its per-interval contributions).

Determinism: the trajectory is a pure function of the episode trace and
the solver configuration — same :func:`repro.market.events.trace_digest`
in, bit-identical :class:`OracleTrajectory` out (property-tested in
``tests/test_oracle_properties.py``).
"""
from __future__ import annotations

import dataclasses
import time as _time
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro import obs
from repro.core import heuristics, lp as lpmod, pareto
from repro.core.scenarios import dead_pin_mask
from repro.market import events as ev
from repro.market.simulator import Fleet

_SLO_TOL = 1e-9          # matches metrics.summarise / fused._SLO_TOL


@dataclasses.dataclass(frozen=True)
class OracleTrajectory:
    """The DP-optimal trajectory for one episode — the reference every
    policy's whole-horizon regret is measured against."""
    policy: str
    episode_seed: int
    trace_digest: str             # events.trace_digest of the input trace
    horizon_s: float
    slo_latency: float
    sla_penalty_rate: float
    switch_cost: float
    # per-interval chosen operating points (aligned with the event grid)
    t0: np.ndarray
    t1: np.ndarray
    makespan: np.ndarray
    cost_rate: np.ndarray         # $ per second, excluding SLA charge
    choice: Tuple[str, ...]       # chosen column label per interval
    # totals
    accrued_cost: float           # raw $ over the episode
    avg_makespan: float           # time-weighted seconds per round
    slo_violation_s: float
    slo_violations: int
    total_cost: float             # accrued + SLA penalty + switch charges
    # DP shape / cost accounting
    n_intervals: int
    n_columns: int
    n_lp_rows: int                # node LPs in the single ladder call
    lp_wall_s: float
    dp_wall_s: float              # total wall (includes lp_wall_s)

    @property
    def durations(self) -> np.ndarray:
        return self.t1 - self.t0


@dataclasses.dataclass(frozen=True)
class _PathColumn:
    """A realised per-interval trajectory offered to the DP as one extra
    column: (makespan, cost_rate) keyed by interval start time."""
    name: str
    points: dict                  # round(t0) key -> (makespan, cost_rate)


def _path_column(result, index: int) -> _PathColumn:
    """Accepts an :class:`~repro.market.simulator.EpisodeResult` or an
    :class:`~repro.market.metrics.EpisodeMetrics`."""
    points = {}
    if hasattr(result, "intervals"):          # EpisodeResult
        name = result.policy
        for r in result.intervals:
            points[round(float(r.t0), 9)] = (float(r.makespan),
                                             float(r.cost_rate))
    else:                                     # EpisodeMetrics
        name = result.policy
        for a, mk, cr in zip(result.t0, result.makespan,
                             result.cost_rate):
            points[round(float(a), 9)] = (float(mk), float(cr))
    return _PathColumn(f"path:{name}#{index}", points)


def _heuristic_candidates(problem, dead, n_weights: int
                          ) -> List[np.ndarray]:
    """The heuristic move set at one interval — identical to
    :meth:`repro.market.policies.ResplitPolicy._plan`'s battery plus the
    cheapest single live platform."""
    from repro.market.policies import _mask_to_alive
    alive = ~dead
    w = np.where(alive, 1.0 / problem.single_platform_latency(), 0.0)
    cands = [heuristics.proportional_split(problem, w)]
    for lam in np.linspace(0.0, 1.0, n_weights):
        cands.append(_mask_to_alive(problem, heuristics.scalarised(
            problem, float(lam)), dead))
    cands.append(heuristics.cheapest_single_platform(problem,
                                                     allowed=alive))
    return cands


def _rate(problem, alloc, slo_latency: float, sla_penalty_rate: float
          ) -> Tuple[float, float, float]:
    """(J, makespan, cost_rate): the true accrual objective in $/s —
    what an interval actually bills under this allocation."""
    mk, cost = heuristics.evaluate(problem, alloc)
    cr = cost / mk
    j = cr + (sla_penalty_rate
              if mk > slo_latency * (1.0 + _SLO_TOL) else 0.0)
    return j, mk, cr


def whole_horizon_oracle(catalog, n, episode: ev.MarketEpisode, *,
                         slo_latency: float,
                         sla_penalty_rate: float = 0.0,
                         n_caps: int = 9, n_weights: int = 9,
                         cap_headroom: float = 1.25,
                         switch_cost: float = 0.0,
                         paths: Sequence = (),
                         linsolve: str = "xla", compact: bool = False,
                         chunk_iters: Optional[int] = None,
                         newton_dtype: str = "float64",
                         compact_mode: str = "device",
                         mesh=None, row_spec=None) -> OracleTrajectory:
    """Solve the whole-horizon DP for one episode.

    ``paths`` takes realised policy runs (``EpisodeResult`` /
    ``EpisodeMetrics``) whose per-interval operating points join the
    DP's move set — passing a policy's own run makes its regret
    non-negative by construction.  ``switch_cost`` charges each plan
    change (default 0, matching the simulator's free replans).
    ``mesh`` / ``row_spec`` shard the single node-LP megabatch.
    """
    from repro.market.policies import _mask_to_alive
    t_start = _time.perf_counter()
    digest = ev.trace_digest(episode)

    # -- interval grid: replay the shadow fleet ------------------------
    fleet = Fleet.from_episode(catalog, n, episode)
    bounds = [0.0] + [float(e.time) for e in episode.events] \
        + [float(episode.horizon_s)]
    probs, deads, pins = [], [], []
    for i in range(len(episode.events) + 1):
        if i > 0:
            fleet.apply_event(episode.events[i - 1])
        probs.append(fleet.problem())
        dead = fleet.dead
        deads.append(dead)
        pins.append(dead_pin_mask(dead, probs[-1].tau))
    n_int = len(probs)
    dts = np.diff(np.asarray(bounds))

    # -- LP megabatch: every (interval, budget) node in ONE ladder call -
    nodes = []
    for p, dead, pin in zip(probs, deads, pins):
        c_l, c_u = pareto._cheap_cost_bounds(p, dead)
        caps = np.linspace(c_l, max(c_u, c_l) * cap_headroom, n_caps)
        nodes.extend(p.node_lp(float(ck), b_fixed0=pin) for ck in caps)
    if mesh is not None:
        row_axes = lpmod._lp_row_axes(mesh, row_spec)
        n_shards = lpmod._n_shards_of(mesh, row_axes)
    else:
        n_shards = 1
    # power-of-two ladder cap: episodes with different event counts then
    # share the same padded widths, so the stacked-IPM compile set stays
    # flat across a whole trace sweep
    ladder_max = 1 << max(0, len(nodes) - 1).bit_length()
    if ladder_max % n_shards:
        ladder_max = -(-len(nodes) // n_shards) * n_shards
    t_lp = _time.perf_counter()
    with obs.span("market.oracle.lp_megabatch", rows=len(nodes),
                  intervals=n_int, seed=episode.seed):
        sols = lpmod.solve_node_lps_ladder(
            nodes, ladder_max=ladder_max, linsolve=linsolve,
            compact=compact, chunk_iters=chunk_iters,
            newton_dtype=newton_dtype, compact_mode=compact_mode,
            mesh=mesh, row_spec=row_spec)
    lp_wall = _time.perf_counter() - t_lp
    xs = np.asarray(sols.x).reshape(n_int, n_caps, -1)

    # -- column battery per interval -----------------------------------
    # layout: heuristics (n_weights + 2) | lp budget grid (n_caps) |
    #         hold chains (one per t=0 candidate) | realised paths
    labels: List[str] = []
    per_interval_allocs: List[List[np.ndarray]] = [[] for _ in probs]
    for i, (p, dead) in enumerate(zip(probs, deads)):
        cands = _heuristic_candidates(p, dead, n_weights)
        cands.extend(_mask_to_alive(p, p.split_node_x(xs[i, j])[0], dead)
                     for j in range(n_caps))
        per_interval_allocs[i] = cands
    labels.extend(["prop"]
                  + [f"scal{j}" for j in range(n_weights)] + ["cheap"]
                  + [f"lp{j}" for j in range(n_caps)])
    n_fresh = len(labels)

    # hold chains: carry each t=0 candidate forward, re-projecting only
    # when a departure strands share — StaticPolicy's exact dynamics
    hold_chains: List[List[np.ndarray]] = []
    for k in range(n_fresh):
        a = per_interval_allocs[0][k]
        chain = [a]
        for i in range(1, n_int):
            stranded = float(a[deads[i]].sum())
            if stranded > 1e-12:
                a = _mask_to_alive(probs[i], a, deads[i])
            chain.append(a)
        hold_chains.append(chain)
    labels.extend(f"hold:{labels[k]}" for k in range(n_fresh))

    path_cols = [_path_column(r, i) for i, r in enumerate(paths)]
    labels.extend(c.name for c in path_cols)
    n_cols = len(labels)

    # -- contribution matrix C[i, k] = dt_i * J_i(k) -------------------
    contrib = np.zeros((n_int, n_cols))
    mk_tab = np.full((n_int, n_cols), np.inf)
    cr_tab = np.full((n_int, n_cols), np.inf)
    for i in range(n_int):
        dt = float(dts[i])
        allocs = per_interval_allocs[i] \
            + [chain[i] for chain in hold_chains]
        for k, a in enumerate(allocs):
            j, mk, cr = _rate(probs[i], a, slo_latency, sla_penalty_rate)
            mk_tab[i, k], cr_tab[i, k] = mk, cr
            contrib[i, k] = dt * j if dt > 0.0 else 0.0
        for c_off, col in enumerate(path_cols):
            k = 2 * n_fresh + c_off
            pt = col.points.get(round(float(bounds[i]), 9))
            if pt is None:
                # the simulator drops zero-length intervals; a missing
                # point on a positive-length one disables the column
                contrib[i, k] = 0.0 if dt <= 0.0 else np.inf
                continue
            mk, cr = pt
            mk_tab[i, k], cr_tab[i, k] = mk, cr
            j = cr + (sla_penalty_rate
                      if mk > slo_latency * (1.0 + _SLO_TOL) else 0.0)
            contrib[i, k] = dt * j if dt > 0.0 else 0.0

    # -- backward induction --------------------------------------------
    v_next = np.zeros(n_cols)
    nxt = np.full((n_int, n_cols), -1, dtype=np.int64)
    for i in range(n_int - 1, -1, -1):
        if i == n_int - 1:
            v = contrib[i].copy()
        else:
            best_k = int(np.argmin(v_next))
            stay = v_next
            jump = v_next[best_k] + switch_cost
            take_stay = stay <= jump
            nxt[i] = np.where(take_stay, np.arange(n_cols), best_k)
            v = contrib[i] + np.where(take_stay, stay, jump)
        v_next = v
    k0 = int(np.argmin(v_next))
    total = float(v_next[k0])

    # -- forward reconstruction ----------------------------------------
    ks = [k0]
    for i in range(n_int - 1):
        ks.append(int(nxt[i][ks[-1]]))
    ks_arr = np.asarray(ks)
    mk_path = mk_tab[np.arange(n_int), ks_arr]
    cr_path = cr_tab[np.arange(n_int), ks_arr]
    live = dts > 0.0
    viol = live & (mk_path > slo_latency * (1.0 + _SLO_TOL))
    accrued = float((cr_path[live] * dts[live]).sum())
    viol_s = float(dts[viol].sum())
    horizon = float(episode.horizon_s)
    traj = OracleTrajectory(
        "dp_oracle", episode.seed, digest, horizon,
        float(slo_latency), float(sla_penalty_rate), float(switch_cost),
        np.asarray(bounds[:-1]), np.asarray(bounds[1:]),
        mk_path, cr_path, tuple(labels[k] for k in ks),
        accrued_cost=accrued,
        avg_makespan=float((mk_path[live] * dts[live]).sum()
                           / max(horizon, 1e-12)),
        slo_violation_s=viol_s, slo_violations=int(viol.sum()),
        total_cost=total,
        n_intervals=n_int, n_columns=n_cols, n_lp_rows=len(nodes),
        lp_wall_s=lp_wall, dp_wall_s=_time.perf_counter() - t_start)
    return traj


def oracle_suite(catalog, n, episodes: Sequence[ev.MarketEpisode], *,
                 slo_latencies, sla_penalty_rates=None,
                 paths_by_seed=None, **kw) -> Tuple[OracleTrajectory, ...]:
    """One :func:`whole_horizon_oracle` per episode.  ``paths_by_seed``
    maps episode seed -> sequence of realised runs to fold into that
    episode's move set; scalar or per-episode ``sla_penalty_rates``."""
    rates = sla_penalty_rates
    out = []
    for i, (ep, slo) in enumerate(zip(episodes, slo_latencies)):
        rate = 0.0 if rates is None else (
            float(rates) if np.isscalar(rates) else float(rates[i]))
        paths = () if paths_by_seed is None else tuple(
            paths_by_seed.get(ep.seed, ()))
        out.append(whole_horizon_oracle(
            catalog, n, ep, slo_latency=float(slo),
            sla_penalty_rate=rate, paths=paths, **kw))
    return tuple(out)
