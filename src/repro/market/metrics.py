"""Episode evaluation: traces, SLO accounting, hypervolume-over-time and
regret against the clairvoyant oracle.

An :class:`~repro.market.simulator.EpisodeResult` is a sequence of
inter-event intervals, each executed under a fixed allocation.  This
module reduces those to:

* per-episode traces (makespan / cost-rate / fleet-size over time),
* totals: accrued dollars, time-weighted mean latency, SLO-violation
  seconds and counts, replans and replanning wall time,
* hypervolume-over-time: the 2-D hypervolume of the realised
  (cost-rate, makespan) operating points accumulated up to each event,
* regret: excess accrued cost and time-averaged excess latency versus
  an oracle run of the same episode.

Two oracles exist.  :func:`whole_horizon_regret` measures against the
whole-horizon DP (:func:`repro.market.oracle.whole_horizon_oracle`) and
is **non-negative by construction** when the policy's realised run was
folded into the DP's move set via ``paths`` — the honest headline
number.  :func:`regret` / :func:`regret_table` measure against the
per-interval clairvoyant (:class:`repro.market.policies.OraclePolicy`);
that oracle optimises lexicographic (cost, makespan) per interval, not
the accrual objective, so policies can legitimately beat it — keep it
as a *diagnostic lower-bound*, never a headline (see docs/market.md).
"""
from __future__ import annotations

import bisect
import dataclasses
import warnings
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.market.simulator import EpisodeResult


@dataclasses.dataclass(frozen=True)
class EpisodeMetrics:
    policy: str
    episode_seed: int
    horizon_s: float
    slo_latency: float
    # traces (one entry per inter-event interval)
    t0: np.ndarray
    t1: np.ndarray
    makespan: np.ndarray
    cost_rate: np.ndarray
    n_alive: np.ndarray
    # totals
    accrued_cost: float           # raw $ over the episode
    avg_makespan: float           # time-weighted seconds per round
    slo_violation_s: float        # seconds spent above the SLO
    slo_violations: int           # intervals above the SLO
    replans: int
    replan_wall_s: float          # per-event replanning only
    # one-time t=0 planning / presolve wall seconds
    reset_wall_s: float = 0.0
    # SLA accounting: every second above the SLO is charged this rate,
    # so a policy cannot undercut the oracle on dollars by simply not
    # meeting the latency target.  0 disables the charge.
    sla_penalty_rate: float = 0.0

    @property
    def durations(self) -> np.ndarray:
        return self.t1 - self.t0

    @property
    def sla_penalty_cost(self) -> float:
        return self.sla_penalty_rate * self.slo_violation_s

    @property
    def total_cost(self) -> float:
        """Accrued dollars including SLA penalties — the cost that
        regret is measured on."""
        return self.accrued_cost + self.sla_penalty_cost


def summarise(result: EpisodeResult, *,
              sla_penalty_rate: float = 0.0) -> EpisodeMetrics:
    iv = result.intervals
    t0 = np.array([r.t0 for r in iv])
    t1 = np.array([r.t1 for r in iv])
    mk = np.array([r.makespan for r in iv])
    cr = np.array([r.cost_rate for r in iv])
    alive = np.array([r.n_alive for r in iv])
    dt = t1 - t0
    horizon = float(dt.sum())
    viol = mk > result.slo_latency * (1 + 1e-9)
    m = EpisodeMetrics(
        result.policy, result.episode_seed, result.horizon_s,
        result.slo_latency, t0, t1, mk, cr, alive,
        accrued_cost=float((cr * dt).sum()),
        avg_makespan=float((mk * dt).sum() / max(horizon, 1e-12)),
        slo_violation_s=float(dt[viol].sum()),
        slo_violations=int(viol.sum()),
        replans=sum(r.replanned for r in iv),
        replan_wall_s=float(sum(r.replan_wall_s for r in iv
                                if r.replanned)),
        reset_wall_s=float(result.reset_wall_s),
        sla_penalty_rate=float(sla_penalty_rate))
    return m


def hypervolume_over_time(metrics: EpisodeMetrics,
                          ref: Tuple[float, float] = None
                          ) -> Tuple[np.ndarray, np.ndarray]:
    """(times, hv): hypervolume of the realised (cost_rate, makespan)
    operating points accumulated up to each interval end, w.r.t. ``ref``
    (default: 1.1x the episode's worst realised point — pass a shared
    ref to compare policies).

    Computed incrementally: a sorted non-dominated front is maintained
    across intervals and each insertion adjusts only its local strip
    contributions, so an n-interval episode costs O(n log n) total
    instead of the former per-prefix recomputation's O(n^2).
    """
    if ref is None:
        warnings.warn(
            "hypervolume_over_time: using a per-episode default ref "
            "point (1.1x this run's worst realised operating point). "
            "HV curves built from per-policy defaults are NOT comparable "
            "across policies — pass a shared ref=(ref_cost, ref_lat).",
            stacklevel=2)
        ref = (float(metrics.cost_rate.max()) * 1.1,
               float(metrics.makespan.max()) * 1.1)
    ref_c, ref_l = float(ref[0]), float(ref[1])
    # front: costs ascending, latencies strictly descending.  Each front
    # point i owns the strip (c_{i+1} - c_i) * (ref_l - l_i) with
    # c_end = ref_c — the staircase pareto.hypervolume() integrates,
    # decomposed into LOCAL contributions so inserts are cheap.
    fc: List[float] = []
    fl: List[float] = []
    hv = np.empty(len(metrics.t1))
    acc = 0.0
    for i, (c, l) in enumerate(zip(metrics.cost_rate, metrics.makespan)):
        c, l = float(c), float(l)
        if c >= ref_c or l >= ref_l:
            hv[i] = acc                   # outside the ref box: no area
            continue
        pos = bisect.bisect_left(fc, c)
        if (pos > 0 and fl[pos - 1] <= l) or \
           (pos < len(fc) and fc[pos] == c and fl[pos] <= l):
            hv[i] = acc                   # dominated (or a duplicate)
            continue
        k = pos                           # successors the point dominates
        while k < len(fc) and fl[k] >= l:
            k += 1
        nxt_after = fc[k] if k < len(fc) else ref_c
        old = new = 0.0
        if pos > 0:                       # predecessor's strip narrows
            old_nxt = fc[pos] if pos < len(fc) else ref_c
            old += (old_nxt - fc[pos - 1]) * (ref_l - fl[pos - 1])
            new += (c - fc[pos - 1]) * (ref_l - fl[pos - 1])
        for j in range(pos, k):           # strips of dominated points
            nxt = fc[j + 1] if j + 1 < k else nxt_after
            old += (nxt - fc[j]) * (ref_l - fl[j])
        new += (nxt_after - c) * (ref_l - l)
        acc += new - old
        fc[pos:k] = [c]
        fl[pos:k] = [l]
        hv[i] = acc
    return metrics.t1, hv


@dataclasses.dataclass(frozen=True)
class DistributionalRegret:
    """Per-policy cost-regret distribution over a Monte-Carlo trace
    suite.  Regret on each trace is the policy's total episode cost
    minus the best total cost ANY evaluated policy achieved on that
    same trace — so every statistic is >= 0 and the per-trace winner
    contributes exactly 0."""
    policy: str
    n_traces: int
    mean: float
    p50: float
    p90: float
    p95: float
    cvar95: float                 # mean regret over the worst 5% traces
    worst: float


def distributional_regret(costs: Dict[str, np.ndarray], *,
                          alpha: float = 0.95,
                          baseline: Optional[np.ndarray] = None
                          ) -> Dict[str, DistributionalRegret]:
    """Distributional (CVaR / quantile-band) regret across a trace suite.

    ``costs`` maps policy name -> (n_traces,) total episode cost, all
    evaluated on the SAME traces in the same order (e.g. from
    :func:`repro.market.fused.run_suite_fused` totals via
    ``total_cost``).  The per-trace reference is ``baseline`` when given
    (e.g. whole-horizon oracle costs per trace, in suite order) and the
    pointwise best policy otherwise; ``cvar`` averages the worst
    ``1 - alpha`` tail.
    """
    if not costs:
        raise ValueError("no policies")
    mat = np.stack([np.asarray(v, dtype=np.float64)
                    for v in costs.values()])
    if mat.ndim != 2:
        raise ValueError("each policy needs a 1-D per-trace cost array")
    if baseline is not None:
        best = np.asarray(baseline, dtype=np.float64)
        if best.shape != (mat.shape[1],):
            raise ValueError(
                f"baseline has {best.shape} costs, suite has "
                f"{mat.shape[1]} traces — regret needs one oracle cost "
                f"per trace, in suite order")
    else:
        best = mat.min(axis=0)
    n = mat.shape[1]
    k = max(1, int(np.ceil((1.0 - alpha) * n)))   # tail size for CVaR
    out: Dict[str, DistributionalRegret] = {}
    for name, row in zip(costs.keys(), mat):
        r = np.sort(row - best)
        rep = DistributionalRegret(
            name, n, float(r.mean()),
            float(np.quantile(r, 0.50)), float(np.quantile(r, 0.90)),
            float(np.quantile(r, alpha)), float(r[-k:].mean()),
            float(r[-1]))
        out[name] = rep
    return out


def distributional_regret_from_totals(suites, *, alpha: float = 0.95,
                                      sla_penalty_rates=None,
                                      oracles=None
                                      ) -> Dict[str, DistributionalRegret]:
    """:func:`distributional_regret` over ``{policy: [FusedTotals, ...]}``
    suites (see :func:`repro.market.fused.run_suite_fused`).
    ``sla_penalty_rates`` is a scalar or per-trace sequence charged on
    SLO-violating seconds.

    ``oracles`` (optional) is one whole-horizon
    :class:`~repro.market.oracle.OracleTrajectory` per trace, in suite
    order: their ``total_cost`` becomes the per-trace regret baseline.

    Comparability is enforced, not assumed: every policy's totals must
    carry the same trace digests in the same order (falling back to
    episode seeds only for totals predating the digest field), and the
    oracle trajectories must match those digests trace-for-trace — a
    mismatch raises ``ValueError`` instead of silently zipping
    different traces together.
    """
    def rate_for(i):
        if sla_penalty_rates is None:
            return 0.0
        if np.isscalar(sla_penalty_rates):
            return float(sla_penalty_rates)
        return float(sla_penalty_rates[i])

    ref = None          # (policy name, per-trace (seed, digest) tuple)
    costs: Dict[str, np.ndarray] = {}
    for name, totals in suites.items():
        ident = tuple((t.episode_seed, getattr(t, "trace_digest", None))
                      for t in totals)
        if ref is None:
            ref = (name, ident)
        elif ident != ref[1]:
            raise ValueError(
                f"policy {name!r} scored a different trace suite than "
                f"{ref[0]!r} (trace digest/seed mismatch) — regret "
                f"needs matched traces")
        costs[name] = np.array([t.total_cost(rate_for(i))
                                for i, t in enumerate(totals)])
    baseline = None
    if oracles is not None:
        oracles = list(oracles)
        n_traces = len(ref[1])
        if len(oracles) != n_traces:
            raise ValueError(f"{len(oracles)} oracle trajectories for "
                             f"{n_traces} traces")
        for i, ((seed, digest), o) in enumerate(zip(ref[1], oracles)):
            if o.episode_seed != seed or (digest is not None and
                                          o.trace_digest != digest):
                raise ValueError(
                    f"oracle trajectory {i} solved a different trace "
                    f"(trace digest/seed mismatch) — regret needs "
                    f"matched traces")
        baseline = np.array([o.total_cost for o in oracles])
    return distributional_regret(costs, alpha=alpha, baseline=baseline)


@dataclasses.dataclass(frozen=True)
class RegretReport:
    """Policy-vs-oracle on one episode (aligned interval-by-interval —
    both runs replay the same event trace)."""
    policy: str
    episode_seed: int
    cost_regret: float            # $ accrued beyond the oracle
    makespan_regret: float        # time-averaged excess seconds per round
    slo_excess_s: float           # SLO-violation seconds beyond oracle
    replans: int
    replan_wall_s: float


def regret(policy: EpisodeMetrics, oracle: EpisodeMetrics) -> RegretReport:
    """Policy vs the PER-INTERVAL clairvoyant — a diagnostic lower
    bound on achievable cost, not a floor: policies can legitimately go
    negative here (see :func:`whole_horizon_regret` for the honest,
    non-negative contract)."""
    if len(policy.t1) != len(oracle.t1):
        raise ValueError("episodes do not align (different event traces)")
    dt = policy.durations
    horizon = float(dt.sum())
    rep = RegretReport(
        policy.policy, policy.episode_seed,
        cost_regret=policy.total_cost - oracle.total_cost,
        makespan_regret=float(((policy.makespan - oracle.makespan)
                               * dt).sum() / max(horizon, 1e-12)),
        slo_excess_s=policy.slo_violation_s - oracle.slo_violation_s,
        replans=policy.replans,
        replan_wall_s=policy.replan_wall_s)
    return rep


def whole_horizon_regret(policy, oracle) -> RegretReport:
    """Policy vs the whole-horizon DP oracle on one episode.

    ``policy`` is an :class:`EpisodeMetrics` (Python-loop run) or a
    :class:`~repro.market.fused.FusedTotals` (fused replay); ``oracle``
    an :class:`~repro.market.oracle.OracleTrajectory` solved on the SAME
    trace — seed and (when available) trace digest are checked, a
    mismatch raises.  ``cost_regret >= 0`` whenever the policy's
    realised run was folded into the oracle's move set (``paths=``);
    the SLA penalty rates must agree for the comparison to be $-fair.
    """
    if policy.episode_seed != oracle.episode_seed:
        raise ValueError(
            f"policy ran seed {policy.episode_seed}, oracle solved seed "
            f"{oracle.episode_seed} — regret needs matched traces")
    digest = getattr(policy, "trace_digest", None)
    if digest is not None and digest != oracle.trace_digest:
        raise ValueError("policy and oracle trace digests differ — "
                         "regret needs matched traces")
    if hasattr(policy, "total_cost") and callable(policy.total_cost):
        # FusedTotals: charge the oracle's SLA rate for a fair total
        total = policy.total_cost(oracle.sla_penalty_rate)
    else:
        total = policy.total_cost
    rep = RegretReport(
        policy.policy, policy.episode_seed,
        cost_regret=total - oracle.total_cost,
        makespan_regret=policy.avg_makespan - oracle.avg_makespan,
        slo_excess_s=policy.slo_violation_s - oracle.slo_violation_s,
        replans=policy.replans,
        replan_wall_s=getattr(policy, "replan_wall_s", 0.0))
    return rep


def regret_table(results: List[EpisodeResult],
                 oracle_results: List[EpisodeResult], *,
                 sla_penalty_rate: float = 0.0
                 ) -> Dict[str, Dict[str, float]]:
    """Aggregate per-policy mean regret over an episode suite, against
    the PER-INTERVAL clairvoyant (diagnostic lower bound — see
    :func:`whole_horizon_regret_table` for the non-negative contract).

    ``results`` may hold several policies x episodes; ``oracle_results``
    holds one oracle run per episode (matched by seed).
    ``sla_penalty_rate`` may also be a ``{seed: rate}`` mapping when the
    charge is episode-specific.
    """
    def rate_for(seed):
        if isinstance(sla_penalty_rate, dict):
            return sla_penalty_rate[seed]
        return sla_penalty_rate

    oracles = {r.episode_seed:
               summarise(r, sla_penalty_rate=rate_for(r.episode_seed))
               for r in oracle_results}
    rows: Dict[str, List[RegretReport]] = {}
    for r in results:
        rep = regret(summarise(r, sla_penalty_rate=rate_for(
            r.episode_seed)), oracles[r.episode_seed])
        rows.setdefault(r.policy, []).append(rep)
    out: Dict[str, Dict[str, float]] = {}
    for policy, reps in rows.items():
        out[policy] = dict(
            cost_regret=float(np.mean([r.cost_regret for r in reps])),
            makespan_regret=float(np.mean([r.makespan_regret
                                           for r in reps])),
            slo_excess_s=float(np.mean([r.slo_excess_s for r in reps])),
            replans=float(np.mean([r.replans for r in reps])),
            replan_wall_s=float(np.mean([r.replan_wall_s
                                         for r in reps])))
    return out


def whole_horizon_regret_table(results: List[EpisodeResult],
                               oracles, *,
                               sla_penalty_rate: float = 0.0
                               ) -> Dict[str, Dict[str, float]]:
    """Aggregate per-policy mean WHOLE-HORIZON regret over a suite.

    ``oracles`` maps episode seed -> the DP
    :class:`~repro.market.oracle.OracleTrajectory` for that trace.  Pass
    each policy's runs into the oracle solve via ``paths=`` to make
    every ``cost_regret`` here non-negative by construction.
    ``sla_penalty_rate`` may be a ``{seed: rate}`` mapping.
    """
    def rate_for(seed):
        if isinstance(sla_penalty_rate, dict):
            return sla_penalty_rate[seed]
        return sla_penalty_rate

    rows: Dict[str, List[RegretReport]] = {}
    for r in results:
        m = summarise(r, sla_penalty_rate=rate_for(r.episode_seed))
        rep = whole_horizon_regret(m, oracles[r.episode_seed])
        rows.setdefault(r.policy, []).append(rep)
    out: Dict[str, Dict[str, float]] = {}
    for policy, reps in rows.items():
        out[policy] = dict(
            cost_regret=float(np.mean([r.cost_regret for r in reps])),
            makespan_regret=float(np.mean([r.makespan_regret
                                           for r in reps])),
            slo_excess_s=float(np.mean([r.slo_excess_s for r in reps])),
            replans=float(np.mean([r.replans for r in reps])),
            replan_wall_s=float(np.mean([r.replan_wall_s
                                         for r in reps])))
    return out
