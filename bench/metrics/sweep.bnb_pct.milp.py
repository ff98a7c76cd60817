"""Share of a trade-off sweep spent in its lockstep B&B phase, %: the
``pareto.bnb`` spans over the ``pareto.sweep`` spans (the rest is the
width-1 anchor and the stacked relaxation), each less the time the run
spent starting or stopping the profiler in it."""
from bench import readers


def _own_ns(obs, name: str) -> int:
    return sum(s.dur_ns - sum(max(0, min(s.ts_ns + s.dur_ns, b)
                                  - max(s.ts_ns, a))
                              for a, b in obs.profiler_ns)
               for s in readers.spans(obs, name))


def read(obs):
    sweep = _own_ns(obs, "pareto.sweep")
    if sweep <= 0:
        return None
    return 100.0 * _own_ns(obs, "pareto.bnb") / sweep
