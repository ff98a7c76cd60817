"""Share of a trade-off sweep spent seeding B&B incumbents on the host,
%: the ``milp.seed`` spans (the heuristic battery and warm starts, one
per tree, the anchor's included) over the ``pareto.sweep`` spans, each
less the time the run spent starting or stopping the profiler in it."""
from bench import readers


def _own_ns(obs, name: str) -> int:
    return sum(s.dur_ns - sum(max(0, min(s.ts_ns + s.dur_ns, b)
                                  - max(s.ts_ns, a))
                              for a, b in obs.profiler_ns)
               for s in readers.spans(obs, name))


def read(obs):
    sweep = _own_ns(obs, "pareto.sweep")
    seed = _own_ns(obs, "milp.seed")
    if sweep <= 0 or not readers.spans(obs, "milp.seed"):
        return None
    return 100.0 * seed / sweep
