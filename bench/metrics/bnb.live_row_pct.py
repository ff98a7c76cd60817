"""Share of the lockstep B&B's batch rows that held a live node, %:
``milp.nodes`` over ``milp.batch_rows`` (padding fills the rest)."""


def read(obs):
    rows = obs.counters.get("milp.batch_rows", 0)
    if not rows:
        return None
    return 100.0 * obs.counters.get("milp.nodes", 0) / rows
