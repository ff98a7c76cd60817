"""Repeated regret sweeps through the fused market replay.

Set-up draws the mix's episodes from the seed over the configuration's
spot market, keeps at most the mix's ``event_slots`` events of each and
pads every trace to that count (so every seed compiles and runs the same
shapes), and starts each fleet on the latency-proportional split of its
initial instances.  The window then replays the whole suite again and again
with ``fused.run_episodes_vmapped`` under the re-split policy, as
scoring a replanning policy does.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import data, episodes
from bench.reference import episodes as ref_ep


@dataclasses.dataclass
class State:
    mix: dict
    cat: dict
    eps: list
    slos: np.ndarray
    alloc0s: np.ndarray
    sample: np.ndarray
    args: tuple = None
    kwargs: dict = None


def catalog(cfg: dict, root, seed: int) -> dict:
    """The market's kind catalogue: one kind per platform of the base
    deployment, fitted from the seed."""
    m = data.tenant_models(data.load_config(root, cfg["problem_from"]),
                           seed)[0]
    return dict(beta=m["beta"], gamma=m["gamma"], rho=m["rho"], pi=m["pi"],
                n=m["n"], names=m["names"])


def setup(cfg: dict, mix: dict, seed: int, seconds: float, *, root=None,
          log=print) -> State:
    from repro.market import events as ev
    from repro.market import simulator
    from repro.core.problem import AllocationProblem
    cat = catalog(cfg, root, seed)
    t0 = time.perf_counter()
    n_eps = int(mix["n_episodes"])
    pad = int(mix["event_slots"])
    eps = []
    for i in range(n_eps):
        e = episodes.generate(cat["names"], cfg,
                              data.rng(seed, data.EPISODES, i))
        e["events"] = e["events"][:pad]
        eps.append(e)
    slos = np.empty(n_eps)
    alloc0s = []
    for i, e in enumerate(eps):
        occ, kind, _ = episodes.slot_events(e)
        lat = ((cat["beta"] * cat["n"][None, :] + cat["gamma"])
               .sum(axis=1))[kind]
        slos[i] = float(cfg["slo_factor"]) * float(lat[occ].min())
        alloc0s.append(ref_ep.initial_split(cat, occ, kind))
    log(f"{n_eps} episodes in {time.perf_counter() - t0:.3f} s")
    problem = AllocationProblem(cat["beta"], cat["gamma"], cat["n"],
                                cat["rho"], cat["pi"], cat["names"])
    kinds = simulator.catalog_from_problem(problem)
    mine = []
    for i, e in enumerate(eps):
        mine.append(ev.MarketEpisode(
            i, e["horizon_s"], cat["names"], e["max_platforms"],
            tuple(e["initial"]),
            tuple(ev.MarketEvent(t, k, name, tuple(p.items()))
                  for t, k, name, p in e["events"])))
    tensors = [ev.materialise_events(e, pad_to=pad) for e in mine]
    st = State(mix, cat, eps, slos, np.stack(alloc0s),
               data.rng(seed, data.SAMPLE).choice(
                   n_eps, size=min(int(mix["check_episodes"]), n_eps),
                   replace=False))
    st.args = (kinds, problem.n, mine)
    st.kwargs = dict(policy_kind=mix["policy"], slo_latencies=slos,
                     alloc0s=st.alloc0s, n_weights=int(mix["n_weights"]),
                     tensors=tensors,
                     episode_chunk=mix.get("episode_chunk"))
    t0 = time.perf_counter()
    _call(st)
    log(f"warm replay in {time.perf_counter() - t0:.3f} s")
    return st


def _call(st: State):
    from repro.market import fused
    return fused.run_episodes_vmapped(*st.args, **st.kwargs)


_FIELDS = ("accrued_cost", "avg_makespan", "slo_violation_s",
           "slo_violations", "replans")


def window(st: State, seconds: float) -> dict:
    calls = []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        out = _call(st)
        calls.append([{f: getattr(out[i], f) for f in _FIELDS}
                      for i in st.sample])
    t_end = time.perf_counter()
    return dict(attempted=len(calls), failed=0, calls=calls, t0=t0,
                t_end=t_end, n_episodes=len(st.eps))


def end_to_end(raw: dict) -> dict:
    return {"episodes_per_s": len(raw["calls"]) * raw["n_episodes"]
            / (raw["t_end"] - raw["t0"])}


def release(st: State) -> None:
    st.args = st.kwargs = None


def reference_totals(st: State, i: int, dtype=np.float64) -> dict:
    e = st.eps[i]
    occ, kind, evs = episodes.slot_events(e)
    return ref_ep.replay(st.cat, occ, kind, evs, e["horizon_s"],
                         st.slos[i], st.alloc0s[i], int(st.mix["n_weights"]),
                         dtype)


def check(st: State, raw: dict, seed: int, log=print) -> dict:
    """The seeded sample of episodes, as every call replayed them,
    against the plain episode loop."""
    gap = 0.0
    for j, i in enumerate(st.sample):
        ref = reference_totals(st, int(i))
        for call in raw["calls"]:
            gap = max(gap, ref_ep.totals_gap(call[j], ref,
                                             st.eps[i]["horizon_s"]))
    log(f"checked {len(st.sample)} episodes x {len(raw['calls'])} calls "
        f"against the plain loop")
    return {"episode_gap": {"value": gap,
                            "limit": st.mix["limits"]["episode_gap"]}}
