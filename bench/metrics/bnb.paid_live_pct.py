"""Share of the rows the lockstep B&B's stacked solves paid that held a
live node, %: ``milp.nodes`` over ``milp.dispatch_rows`` (each round pads
its nodes up to the width it is dispatched at)."""


def read(obs):
    rows = obs.counters.get("milp.dispatch_rows", 0)
    if not rows:
        return None
    return 100.0 * obs.counters.get("milp.nodes", 0) / rows
