"""Host self time of a lockstep B&B round, ms: each ``milp.round`` span
less the ``lp.solve_stacked`` spans inside it on the same thread, and
less the time the run spent starting or stopping the profiler in it."""
from bench import readers


def read(obs):
    rounds = readers.spans(obs, "milp.round")
    if not rounds:
        return None
    solves = readers.spans(obs, "lp.solve_stacked")
    own = 0.0
    for r in rounds:
        end = r.ts_ns + r.dur_ns
        inner = sum(s.dur_ns for s in solves if s.tid == r.tid
                    and r.ts_ns <= s.ts_ns and s.ts_ns + s.dur_ns <= end)
        inner += sum(max(0, min(end, b) - max(r.ts_ns, a))
                     for a, b in obs.profiler_ns)
        own += (r.dur_ns - inner) * 1e-9
    return own / len(rounds) * 1e3
