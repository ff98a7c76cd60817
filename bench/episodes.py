"""Spot-market event traces made from the run's seed.

A copy of the superposed-Poisson episode generator of the repo's market
package, kept here so that the traffic of a regret sweep cannot move
with the program.  An episode is plain data: the instances alive at
t=0 and a time-ordered list of events ``(time, kind, instance,
payload)``; the drivers convert it into the program's own types.

Rates are events per horizon.  A shadow fleet keeps every event
applicable: at least one instance stays alive, the fleet never exceeds
its slots, recoveries pair with an active degradation.
"""
from __future__ import annotations

import numpy as np

ARRIVAL, DEPARTURE, PRICE_TICK, DEGRADE, RECOVER, PRICE_SHOCK, CONTENTION = (
    "arrival", "departure", "price_tick", "degrade", "recover",
    "price_shock", "contention")
_SHOCK, _STORM, _CONTEND = "_shock_burst", "_storm_burst", "_contention"
_PROCESSES = (ARRIVAL, DEPARTURE, PRICE_TICK, DEGRADE, RECOVER,
              _SHOCK, _STORM, _CONTEND)


def generate(kind_names, cfg: dict, r: np.random.Generator) -> dict:
    """One episode over the catalogue ``kind_names`` under the market
    parameters of a configuration (``rates`` and the shape keys)."""
    k = len(kind_names)
    horizon = float(cfg["horizon_s"])
    max_platforms = int(cfg["max_platforms"])
    rt = cfg["rates"]
    uid = 0
    fleet = {}
    initial = []
    for _ in range(int(cfg["n_initial"])):
        kind = int(r.integers(k))
        name = f"{kind_names[kind]}#{uid}"
        uid += 1
        fleet[name] = dict(kind=kind, degraded=False, price=1.0)
        initial.append((name, kind))

    rates = np.array([rt["arrival_rate"], rt["departure_rate"],
                      rt["price_rate"], rt["degrade_rate"],
                      rt["recover_rate"], rt["shock_rate"],
                      rt["storm_rate"], rt["contention_rate"]], np.float64)
    per_s = rates.sum() / horizon
    cum = np.cumsum(rates / rates.sum())
    droughts = []
    if rt["drought_rate"] > 0.0:
        for _ in range(int(r.poisson(rt["drought_rate"]))):
            start = float(r.uniform(0.0, horizon))
            dur = float(r.uniform(*cfg["drought_span"])) * horizon
            droughts.append((start, start + dur))

    def burst_times(at: float, count: int):
        span = min(1.0, 0.5 * (horizon - at))
        step = span / max(1, count)
        return [at + i * step for i in range(count)]

    events = []
    t = 0.0
    while True:
        t += float(r.exponential(1.0 / per_s))
        if t >= horizon:
            break
        proc = _PROCESSES[int(np.searchsorted(cum, r.random(), side="right"))]
        alive = sorted(fleet)
        if proc == ARRIVAL:
            kind = int(r.integers(k))
            if len(alive) >= max_platforms:
                continue
            if any(s <= t < e for s, e in droughts):
                continue
            name = f"{kind_names[kind]}#{uid}"
            uid += 1
            fleet[name] = dict(kind=kind, degraded=False, price=1.0)
            events.append((t, ARRIVAL, name, {"kind_index": kind}))
        elif proc == DEPARTURE:
            if len(alive) <= 1:
                continue
            name = alive[int(r.integers(len(alive)))]
            del fleet[name]
            events.append((t, DEPARTURE, name, {}))
        elif proc == PRICE_TICK:
            name = alive[int(r.integers(len(alive)))]
            step = float(np.exp(r.normal(0.0, cfg["price_sigma"])))
            scale = float(np.clip(fleet[name]["price"] * step, 0.25, 4.0))
            fleet[name]["price"] = scale
            events.append((t, PRICE_TICK, name, {"price_scale": scale}))
        elif proc == DEGRADE:
            healthy = [n for n in alive if not fleet[n]["degraded"]]
            scale = float(r.uniform(*cfg["degrade_range"]))
            if not healthy:
                continue
            name = healthy[int(r.integers(len(healthy)))]
            fleet[name]["degraded"] = True
            events.append((t, DEGRADE, name, {"beta_scale": scale}))
        elif proc == RECOVER:
            degraded = [n for n in alive if fleet[n]["degraded"]]
            if not degraded:
                continue
            name = degraded[int(r.integers(len(degraded)))]
            fleet[name]["degraded"] = False
            events.append((t, RECOVER, name, {"beta_scale": 1.0}))
        elif proc == _SHOCK:
            regions = max(1, int(cfg["n_regions"]))
            factor = float(np.exp(r.normal(0.0, cfg["shock_sigma"])))
            region = int(r.integers(regions))
            hit = [n for n in alive if fleet[n]["kind"] % regions == region]
            if not hit:
                continue
            times = burst_times(t, len(hit))
            for at, name in zip(times, hit):
                idio = float(np.exp(r.normal(0.0, cfg["shock_idio_sigma"])))
                scale = float(np.clip(fleet[name]["price"] * factor * idio,
                                      0.05, 10.0))
                fleet[name]["price"] = scale
                events.append((at, PRICE_SHOCK, name,
                               {"price_scale": scale, "factor": factor}))
            t = times[-1]
        elif proc == _STORM:
            if len(alive) <= 1:
                continue
            max_kill = max(1, int(cfg["storm_frac"] * (len(alive) - 1)))
            n_kill = 1 + int(r.integers(max_kill))
            victims = [alive[i] for i in
                       r.choice(len(alive), size=n_kill, replace=False)]
            times = burst_times(t, len(victims))
            for at, name in zip(times, victims):
                del fleet[name]
                events.append((at, DEPARTURE, name, {}))
            t = times[-1]
        else:
            name = alive[int(r.integers(len(alive)))]
            if float(r.random()) < cfg["contention_clear_p"]:
                scale = 1.0
            else:
                scale = float(r.uniform(*cfg["contention_range"]))
            events.append((t, CONTENTION, name, {"throughput_scale": scale}))
    return dict(horizon_s=horizon, max_platforms=max_platforms,
                initial=initial, events=events)


def slot_events(episode: dict):
    """Resolve instance names to fleet slots by the first-empty-slot rule:
    ``(occupied (S,), kind (S,))`` at t=0 and one ``(time, kind, slot,
    payload)`` per event."""
    s = episode["max_platforms"]
    slots = [None] * s
    occ = np.zeros(s, bool)
    kind0 = np.zeros(s, np.int64)

    def occupy(name):
        i = slots.index(None)
        slots[i] = name
        return i

    for name, kind in episode["initial"]:
        i = occupy(name)
        occ[i] = True
        kind0[i] = kind
    out = []
    for t, kind, name, payload in episode["events"]:
        if kind == ARRIVAL:
            i = occupy(name)
        else:
            i = slots.index(name)
            if kind == DEPARTURE:
                slots[i] = None
        out.append((t, kind, i, payload))
    return occ, kind0, out
