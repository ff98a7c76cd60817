"""Lockstep B&B rounds per completed sweep (``milp.rounds`` counter)."""


def read(obs):
    sweeps = len(obs.raw.get("sweeps") or ())
    rounds = obs.counters.get("milp.rounds", 0)
    return rounds / sweeps if sweeps and rounds else None
