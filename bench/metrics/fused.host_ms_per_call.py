"""Host time per fused replay call, ms: each ``market.episodes_vmapped``
span's wall time less the device busy time inside it."""


def read(obs):
    calls = obs.trace.host.get("market.episodes_vmapped") or []
    if not calls:
        return None
    wall = sum(e - s for s, e, _ in calls) * 1e-9
    busy = obs.trace.busy_within((s, e) for s, e, _ in calls)
    return (wall - busy) / len(calls) * 1e3
