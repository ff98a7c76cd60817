"""Device busy time per fused replay call, ms: the trace's busy time
inside each ``market.episodes_vmapped`` span."""


def read(obs):
    calls = obs.trace.host.get("market.episodes_vmapped") or []
    if not calls:
        return None
    busy = obs.trace.busy_within((s, e) for s, e, _ in calls)
    return busy / len(calls) * 1e3 if busy > 0 else None
