"""Fused whole-episode replay: one ``lax.scan`` device program per
episode, vmappable across thousands of sampled event traces.

The Python event loop in :func:`repro.market.simulator.run_episode`
closes one interval per market event with a host round-trip per step —
fine for scoring a handful of episodes, hopeless for the distributional
(CVaR / quantile-band) regret the paper's Monte-Carlo claim actually
needs.  This module replays the SAME episode semantics over the
pre-materialised :class:`repro.market.events.EventTensor` form of a
trace:

* fleet state is five flat arrays (occupied / kind / beta-scale /
  price-scale / contention-scale per slot) stepped branchlessly by
  integer event ids — covering the megadiversity kinds (correlated
  price shocks, preemption storms, droughts, contention) as well as
  the base five;
* each scan step closes the standing interval (the jnp port of
  :func:`repro.core.heuristics.evaluate` against the penalised
  fixed-shape problem), applies the event, and replans through a fused
  policy (jnp ports of the static re-projection and the scalarised
  re-split battery);
* episode totals (accrued cost, time-weighted makespan, SLO-violation
  seconds/intervals, replans) accumulate in-carry, in strong dtypes.

``vmap`` over the episode axis turns a 10^3-trace Monte-Carlo sweep into
ONE compiled call; the Python loop stays the parity oracle (totals agree
to ~1e-12 relative — asserted at 1e-8 in tests).  Fused compiles are
attributed via ``obs.record_compile("episode", ...)``; the stacked-IPM
jit caches are untouched, so ``lp.stacked_compile_count`` stays flat
across fused replays by construction (and tests assert it).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.scenarios import DEAD_PENALTY
from repro.market import events as ev
from repro.market.events import EventTensor, MarketEpisode

_SLO_TOL = 1e-9          # matches metrics.summarise / select_cheapest_slo


# ---------------------------------------------------------------------------
# Catalogue + problem in array form
# ---------------------------------------------------------------------------

def fused_catalog(catalog, n) -> Tuple[jnp.ndarray, ...]:
    """Stack a :class:`PlatformKind` catalogue into device arrays:
    ``(beta (K,tau), gamma (K,tau), rho (K,), pi (K,), n (tau,))``."""
    cat_beta = jnp.asarray(np.stack([k.beta for k in catalog]))
    cat_gamma = jnp.asarray(np.stack([k.gamma for k in catalog]))
    cat_rho = jnp.asarray(np.array([k.rho for k in catalog]))
    cat_pi = jnp.asarray(np.array([k.pi for k in catalog]))
    return cat_beta, cat_gamma, cat_rho, cat_pi, jnp.asarray(
        np.asarray(n, dtype=np.float64))


def _problem_arrays(cat, occ, kind, bsc, psc, csc):
    """The penalised fixed-shape problem for a fleet state — the jnp port
    of :meth:`Fleet.problem` (empty slots borrow kind 0 via the reset-on-
    departure convention and are dead-penalised).  ``csc`` is the
    multi-tenant contention scale (unit when no noisy neighbour)."""
    cat_beta, cat_gamma, cat_rho, cat_pi, n = cat
    scale = jnp.where(occ, 1.0, DEAD_PENALTY)
    beta = cat_beta[kind] * bsc[:, None] * csc[:, None] * scale[:, None]
    gamma = cat_gamma[kind] * scale[:, None]
    return beta * n[None, :], gamma, cat_rho[kind], cat_pi[kind] * psc


def _evaluate(beta_n, gamma, rho, pi, alloc):
    """jnp port of :func:`repro.core.heuristics.evaluate`."""
    setup = (alloc > 1e-12).astype(jnp.float64)
    g_l = (beta_n * alloc + gamma * setup).sum(axis=1)
    makespan = g_l.max()
    cost = (jnp.ceil(g_l / rho - 1e-12) * pi).sum()
    return makespan, cost


def _single_platform(beta_n, gamma, rho, pi):
    lat = (beta_n + gamma).sum(axis=1)
    return lat, jnp.ceil(lat / rho) * pi


def _project_to_alive(beta_n, gamma, alloc, alive):
    """jnp port of :func:`repro.core.milp._project_to_allocation` with an
    ``allowed`` mask: zero dead rows, refill empty columns
    latency-proportionally, renormalise."""
    a = jnp.maximum(alloc, 0.0)
    a = jnp.where(alive[:, None], a, 0.0)
    colsum = a.sum(axis=0)
    empty = colsum <= 1e-9
    lat = (beta_n + gamma).sum(axis=1)
    w = jnp.where(alive, 1.0 / lat, 0.0)
    fill = (w / jnp.maximum(w.sum(), 1e-300))[:, None]
    a = jnp.where(empty[None, :], fill, a)
    return a / a.sum(axis=0)[None, :]


def _cheapest_single(cost_1p, tau):
    i = jnp.argmin(cost_1p)
    mu = cost_1p.shape[0]
    return jnp.tile((jnp.arange(mu) == i).astype(jnp.float64)[:, None],
                    (1, tau))


def _proportional_split(weights, tau):
    w = jnp.maximum(weights, 0.0)
    share = w / jnp.maximum(w.sum(), 1e-300)
    return jnp.tile(share[:, None], (1, tau))


def _scalarised(lat_1p, cost_1p, cost_weight: float, tau):
    """jnp port of :func:`repro.core.heuristics.scalarised` (static
    ``cost_weight``, so the quantile cutoff branch resolves at trace
    time)."""
    if cost_weight >= 1.0:
        return _cheapest_single(cost_1p, tau)
    lat_n = lat_1p / lat_1p.max()
    cost_n = cost_1p / cost_1p.max()
    score = (1.0 - cost_weight) * lat_n + cost_weight * cost_n
    weights = 1.0 / jnp.maximum(score, 1e-12)
    cutoff = jnp.quantile(score, max(0.05, 1.0 - cost_weight))
    weights = jnp.where(score <= cutoff, weights, 0.0)
    prop = _proportional_split(weights, tau)
    return jnp.where(weights.sum() > 0, prop,
                     _cheapest_single(cost_1p, tau))


def _select_cheapest_slo(mks, costs, cands, slo):
    """jnp port of :func:`repro.market.policies.select_cheapest_slo`:
    cheapest candidate meeting the SLO (lexicographic (cost, makespan)),
    fastest when none does."""
    feas = mks <= slo * (1.0 + _SLO_TOL)
    order = jnp.lexsort((mks, jnp.where(feas, costs, jnp.inf)))
    best = order[0]
    fastest = jnp.argmin(mks)
    pick = jnp.where(feas.any(), best, fastest)
    return cands[pick]


# ---------------------------------------------------------------------------
# Fused replay
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FusedTotals:
    """Episode totals produced by the fused replay — the same quantities
    :func:`repro.market.metrics.summarise` reduces the Python loop's
    interval records to (traces are not materialised on device)."""
    policy: str
    episode_seed: int
    horizon_s: float
    slo_latency: float
    accrued_cost: float
    avg_makespan: float
    slo_violation_s: float
    slo_violations: int
    replans: int
    # canonical trace fingerprint (events.trace_digest) of the episode
    # these totals were scored on — what metrics.distributional_regret*
    # match on before comparing across policies / against an oracle
    trace_digest: Optional[str] = None

    def total_cost(self, sla_penalty_rate: float = 0.0) -> float:
        return self.accrued_cost + sla_penalty_rate * self.slo_violation_s


_FUSED_REPLAYS: dict = {}
_FUSED_SIGNATURES: set = set()


def _replan_fn(policy_kind: str, n_weights: int):
    """Fused replanner: ``(cat, fleet state, alloc, slo) -> (alloc',
    replanned)``."""
    if policy_kind == "static":
        def replan(cat, occ, kind, bsc, psc, csc, alloc, slo):
            beta_n, gamma, rho, pi = _problem_arrays(cat, occ, kind, bsc,
                                                     psc, csc)
            stranded = jnp.where(occ[:, None], 0.0, alloc).sum()
            need = stranded > 1e-12
            proj = _project_to_alive(beta_n, gamma, alloc, occ)
            return jnp.where(need, proj, alloc), need

        return replan
    if policy_kind == "resplit":
        lams = [float(v) for v in np.linspace(0.0, 1.0, n_weights)]

        def replan(cat, occ, kind, bsc, psc, csc, alloc, slo):
            beta_n, gamma, rho, pi = _problem_arrays(cat, occ, kind, bsc,
                                                     psc, csc)
            tau = beta_n.shape[1]
            lat_1p, cost_1p = _single_platform(beta_n, gamma, rho, pi)
            w = jnp.where(occ, 1.0 / lat_1p, 0.0)
            cands = [_proportional_split(w, tau)]
            for lam in lams:
                cands.append(_project_to_alive(
                    beta_n, gamma, _scalarised(lat_1p, cost_1p, lam, tau),
                    occ))
            cands = jnp.stack(cands)
            mks, costs = jax.vmap(
                lambda a: _evaluate(beta_n, gamma, rho, pi, a))(cands)
            return _select_cheapest_slo(mks, costs, cands, slo), \
                jnp.asarray(True)

        return replan
    raise ValueError(f"no fused port of policy kind {policy_kind!r}; "
                     f"expected 'static' or 'resplit'")


def _norm_weights(policy_kind: str, n_weights: int) -> int:
    """The static replan has no weight sweep — normalise its key so every
    caller shares one compiled program regardless of the knob."""
    return int(n_weights) if policy_kind == "resplit" else 0


def _episode_fn(policy_kind: str, n_weights: int):
    """Build (and cache) the jitted single-episode scan for one fused
    policy config.  The returned callable takes only arrays, so one
    compilation covers every same-shape episode; vmap over a leading
    episode axis batches traces."""
    key = ("episode", policy_kind, n_weights)
    fn = _FUSED_REPLAYS.get(key)
    if fn is not None:
        return fn

    replan = _replan_fn(policy_kind, n_weights)

    def one_episode(cat_beta, cat_gamma, cat_rho, cat_pi, n, slo,
                    horizon, times, kid, slot, kidx, scale, occ0, kind0,
                    alloc0):
        cat = (cat_beta, cat_gamma, cat_rho, cat_pi, n)
        s = occ0.shape[0]
        slots = jnp.arange(s, dtype=jnp.int32)
        zero = jnp.zeros((), jnp.float64)

        def close(occ, kind, bsc, psc, csc, alloc, dt, acc):
            beta_n, gamma, rho, pi = _problem_arrays(cat, occ, kind, bsc,
                                                     psc, csc)
            mk, cost = _evaluate(beta_n, gamma, rho, pi, alloc)
            live = dt > 0.0
            viol = live & (mk > slo * (1.0 + _SLO_TOL))
            cost_acc, mk_dt, viol_s, viol_n = acc
            return (cost_acc + jnp.where(live, cost / mk * dt, 0.0),
                    mk_dt + jnp.where(live, mk * dt, 0.0),
                    viol_s + jnp.where(viol, dt, 0.0),
                    viol_n + viol.astype(jnp.int32))

        def step(carry, evt):
            occ, kind, bsc, psc, csc, alloc, t_prev, acc, replans = carry
            t, k_id, sl, k_ix, sc = evt
            dt = jnp.maximum(t - t_prev, 0.0)
            acc = close(occ, kind, bsc, psc, csc, alloc, dt, acc)
            # apply the event branchlessly on the touched slot
            hit = slots == sl
            is_arr = k_id == ev.KIND_IDS[ev.ARRIVAL]
            is_dep = k_id == ev.KIND_IDS[ev.DEPARTURE]
            is_price = ((k_id == ev.KIND_IDS[ev.PRICE_TICK]) |
                        (k_id == ev.KIND_IDS[ev.PRICE_SHOCK]))
            is_beta = ((k_id == ev.KIND_IDS[ev.DEGRADE]) |
                       (k_id == ev.KIND_IDS[ev.RECOVER]))
            is_cont = k_id == ev.KIND_IDS[ev.CONTENTION]
            fresh = hit & (is_arr | is_dep)
            occ = jnp.where(hit & is_arr, True,
                            jnp.where(hit & is_dep, False, occ))
            # departures reset the slot to the empty-slot convention
            # (kind 0, unit scales) exactly as Fleet builds a fresh Slot()
            kind = jnp.where(hit & is_arr, k_ix,
                             jnp.where(hit & is_dep, 0, kind))
            bsc = jnp.where(fresh, 1.0,
                            jnp.where(hit & is_beta, sc, bsc))
            psc = jnp.where(fresh, 1.0,
                            jnp.where(hit & is_price, sc, psc))
            csc = jnp.where(fresh, 1.0,
                            jnp.where(hit & is_cont, sc, csc))
            new_alloc, replanned = replan(cat, occ, kind, bsc, psc, csc,
                                          alloc, slo)
            noop = k_id == ev.NOOP_ID
            alloc = jnp.where(noop, alloc, new_alloc)
            replans = replans + jnp.where(noop, 0,
                                          replanned.astype(jnp.int32))
            return (occ, kind, bsc, psc, csc, alloc,
                    jnp.maximum(t, t_prev), acc, replans), None

        acc0 = (zero, zero, zero, jnp.zeros((), jnp.int32))
        carry0 = (occ0, kind0, jnp.ones((s,), jnp.float64),
                  jnp.ones((s,), jnp.float64), jnp.ones((s,), jnp.float64),
                  alloc0, zero, acc0,
                  jnp.ones((), jnp.int32))     # reset counts as a replan
        carry, _ = jax.lax.scan(step, carry0,
                                (times, kid, slot, kidx, scale))
        occ, kind, bsc, psc, csc, alloc, t_prev, acc, replans = carry
        acc = close(occ, kind, bsc, psc, csc, alloc,
                    jnp.maximum(horizon - t_prev, 0.0), acc)
        cost_acc, mk_dt, viol_s, viol_n = acc
        avg_mk = mk_dt / jnp.maximum(horizon, 1e-12)
        return cost_acc, avg_mk, viol_s, viol_n, replans

    fn = jax.jit(one_episode)
    _FUSED_REPLAYS[key] = fn
    return fn


def _record_fused_compile(policy_kind: str, n_weights: int, s: int,
                          tau: int, k: int, n_events: int,
                          n_episodes: int, mesh_shape=None) -> None:
    sig = ("episode", policy_kind, n_weights, s, tau, k, n_events,
           n_episodes, mesh_shape)
    if sig not in _FUSED_SIGNATURES:
        _FUSED_SIGNATURES.add(sig)
        obs.record_compile("episode", policy=policy_kind,
                           n_weights=n_weights, slots=s, tau=tau,
                           catalog=k, n_events=n_events,
                           n_episodes=n_episodes, mesh_shape=mesh_shape)


def run_episode_fused(catalog, n, episode: MarketEpisode, *,
                      policy_kind: str, slo_latency: float,
                      alloc0: np.ndarray, n_weights: int = 9,
                      tensor: Optional[EventTensor] = None,
                      policy_name: Optional[str] = None) -> FusedTotals:
    """Replay ONE episode as a single device program.

    ``alloc0`` is the policy's t=0 plan (computed on the host — resets
    may run a full MILP); every subsequent replan runs fused in-scan.
    Pass a pre-padded ``tensor`` to share one compiled event-count shape
    across a suite.
    """
    tensor = tensor if tensor is not None else ev.materialise_events(
        episode)
    n_weights = _norm_weights(policy_kind, n_weights)
    cat = fused_catalog(catalog, n)
    fn = _episode_fn(policy_kind, n_weights)
    _record_fused_compile(policy_kind, n_weights, tensor.n_slots,
                          int(cat[4].shape[0]), len(catalog),
                          int(tensor.time.shape[0]), 1)
    with obs.span("market.episode_fused", policy=policy_kind,
                  seed=episode.seed, n_events=tensor.n_events):
        out = fn(*cat, jnp.asarray(slo_latency, jnp.float64),
                 jnp.asarray(tensor.horizon_s, jnp.float64),
                 *(jnp.asarray(v) for v in
                   (tensor.time, tensor.kind_id, tensor.slot,
                    tensor.kind_index, tensor.scale, tensor.init_occupied,
                    tensor.init_kind)),
                 jnp.asarray(alloc0, jnp.float64))
        cost, avg_mk, viol_s, viol_n, replans = jax.device_get(out)
    obs.update(counters={"market.fused_episodes": 1,
                         "market.fused_events": tensor.n_events})
    return FusedTotals(policy_name or policy_kind, episode.seed,
                       tensor.horizon_s, float(slo_latency), float(cost),
                       float(avg_mk), float(viol_s), int(viol_n),
                       int(replans), trace_digest=ev.trace_digest(episode))


def run_episodes_vmapped(catalog, n, episodes: Sequence[MarketEpisode], *,
                         policy_kind: str, slo_latencies,
                         alloc0s, n_weights: int = 9,
                         tensors: Optional[Sequence[EventTensor]] = None,
                         policy_name: Optional[str] = None,
                         episode_chunk: Optional[int] = None,
                         mesh=None, row_spec=None
                         ) -> Tuple[FusedTotals, ...]:
    """Replay a whole episode SUITE as one vmapped device call — the
    Monte-Carlo risk engine: 10^3+ sampled traces per policy in a single
    compiled program.  ``slo_latencies`` and ``alloc0s`` are per-episode
    (the t=0 plans come from the host policy reset).

    ``episode_chunk`` bounds device residency for 10^4+ trace suites:
    the episode axis is dispatched in fixed-size vmap chunks (the last
    chunk padded by repeating its final episode, so the jit cache sees
    ONE batch shape), with per-chunk host transfer of the five scalar
    totals.  Episodes are independent, so chunked == unchunked exactly.

    ``mesh`` (+ optional ``row_spec``) shards the episode axis over a
    device mesh with ``shard_map`` — episodes are embarrassingly
    parallel, so the fused scan runs per-shard with zero collectives;
    dispatch widths are padded to a shard multiple.
    """
    episodes = list(episodes)
    tensors = (list(tensors) if tensors is not None
               else list(ev.stack_event_tensors(episodes)))
    evwidths = {t.time.shape[0] for t in tensors}
    if len(evwidths) != 1:
        raise ValueError("tensors not padded to a common event count; "
                         "use events.stack_event_tensors")
    n_eps = len(episodes)
    if episode_chunk is not None and int(episode_chunk) < 1:
        raise ValueError(f"episode_chunk must be >= 1, "
                         f"got {episode_chunk}")
    chunk = n_eps if episode_chunk is None else min(int(episode_chunk),
                                                   n_eps)
    n_weights = _norm_weights(policy_kind, n_weights)
    cat = fused_catalog(catalog, n)
    fn = _episode_fn(policy_kind, n_weights)
    if mesh is not None:
        from repro.core import lp as lpmod
        row_axes = lpmod._lp_row_axes(mesh, row_spec)
        n_shards = lpmod._n_shards_of(mesh, row_axes)
        mesh_shape = lpmod._mesh_shape_of(mesh, row_axes)
        mesh_key = lpmod._mesh_key_of(mesh, row_axes)
    else:
        row_axes, n_shards, mesh_shape, mesh_key = None, 1, None, None
    # ONE dispatch width for the whole suite: the chunk rounded up to a
    # shard multiple — remainder chunks re-pad to it instead of
    # compiling a second shape
    width = -(-chunk // n_shards) * n_shards
    key = ("episode-vmap", policy_kind, n_weights, mesh_key)
    vfn = _FUSED_REPLAYS.get(key)
    if vfn is None:
        vf = jax.vmap(fn, in_axes=(None,) * 5 + (0,) * 10)
        if mesh is not None:
            from jax.sharding import PartitionSpec as PS
            rspec = lpmod._row_pspec(row_axes)
            vf = jax.shard_map(vf, mesh=mesh,
                               in_specs=(PS(),) * 5 + (rspec,) * 10,
                               out_specs=rspec, check_vma=False)
        vfn = jax.jit(vf)
        _FUSED_REPLAYS[key] = vfn
    _record_fused_compile(policy_kind, n_weights, tensors[0].n_slots,
                          int(cat[4].shape[0]), len(catalog),
                          int(evwidths.pop()), width,
                          mesh_shape=mesh_shape)
    # host-side stacks; each dispatch moves only ``width`` episodes to
    # device (the whole point of the memory-aware chunking)
    stack = [np.stack([getattr(t, f) for t in tensors])
             for f in ("time", "kind_id", "slot", "kind_index", "scale",
                       "init_occupied", "init_kind")]
    slos = np.asarray(slo_latencies, dtype=np.float64)
    horizons = np.array([t.horizon_s for t in tensors])
    alloc0s = np.stack([np.asarray(a, dtype=np.float64) for a in alloc0s])
    batched = [slos, horizons] + stack + [alloc0s]
    cost = np.zeros(n_eps)
    avg_mk = np.zeros(n_eps)
    viol_s = np.zeros(n_eps)
    viol_n = np.zeros(n_eps, dtype=np.int64)
    replans = np.zeros(n_eps, dtype=np.int64)
    with obs.span("market.episodes_vmapped", policy=policy_kind,
                  n_episodes=n_eps, chunk=width, n_shards=n_shards):
        for lo in range(0, n_eps, chunk):
            hi = min(lo + chunk, n_eps)
            take = np.arange(lo, hi)
            if take.size < width:      # pad by repeating the last episode
                take = np.concatenate(
                    [take, np.full(width - take.size, hi - 1)])
            out = jax.device_get(vfn(*cat,
                                     *(jnp.asarray(v[take])
                                       for v in batched)))
            k = hi - lo
            for dst, src in zip((cost, avg_mk, viol_s, viol_n, replans),
                                out):
                dst[lo:hi] = src[:k]
    obs.update(counters={"market.fused_episodes": n_eps})
    name = policy_name or policy_kind
    return tuple(
        FusedTotals(name, episodes[i].seed, tensors[i].horizon_s,
                    float(slos[i]), float(cost[i]), float(avg_mk[i]),
                    float(viol_s[i]), int(viol_n[i]), int(replans[i]),
                    trace_digest=ev.trace_digest(episodes[i]))
        for i in range(n_eps))


def run_suite_fused(catalog, n, episodes: Sequence[MarketEpisode],
                    policy, slo_latencies: Sequence[float], *,
                    tensors: Optional[Sequence[EventTensor]] = None,
                    episode_chunk: Optional[int] = None, mesh=None,
                    row_spec=None) -> Tuple[FusedTotals, ...]:
    """Score one policy across a trace suite: host-side ``reset`` per
    episode (resets may run a full MILP), then ONE vmapped device replay
    for every replan.  The policy must expose a ``fused_spec()``
    (see :class:`repro.market.policies.Policy`).  ``episode_chunk`` /
    ``mesh`` / ``row_spec`` pass through to
    :func:`run_episodes_vmapped`."""
    spec = policy.fused_spec()
    if spec is None:
        raise ValueError(f"policy {policy.name!r} has no fused port; "
                         f"use simulator.run_episode")
    kind, n_weights = spec
    from repro.market.simulator import Fleet    # circular at import time
    alloc0s = []
    for ep, slo in zip(episodes, slo_latencies):
        fleet = Fleet.from_episode(catalog, n, ep)
        alloc0s.append(policy.reset(fleet.view(0.0, float(slo))))
    return run_episodes_vmapped(catalog, n, episodes, policy_kind=kind,
                                slo_latencies=slo_latencies,
                                alloc0s=alloc0s, n_weights=n_weights,
                                tensors=tensors, policy_name=policy.name,
                                episode_chunk=episode_chunk, mesh=mesh,
                                row_spec=row_spec)


def fused_compile_count() -> int:
    """Distinct fused-replay signatures seen so far (the fused analogue
    of ``lp.stacked_compile_count`` — flat once every episode shape has
    compiled)."""
    return len(_FUSED_SIGNATURES)
