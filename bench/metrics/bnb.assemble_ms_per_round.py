"""Host time per lockstep B&B round spent building, padding, stacking
and copying its node LPs to the device, ms: the ``milp.assemble`` spans
plus the ``lp.put`` spans inside each ``milp.round`` on its thread."""
from bench import readers


def read(obs):
    rounds = readers.spans(obs, "milp.round")
    assemble = readers.spans(obs, "milp.assemble")
    if not rounds or not assemble:
        return None
    puts = readers.spans(obs, "lp.put")
    inner = sum(p.dur_ns for p in puts if any(
        p.tid == r.tid and r.ts_ns <= p.ts_ns
        and p.ts_ns + p.dur_ns <= r.ts_ns + r.dur_ns for r in rounds))
    total = sum(s.dur_ns for s in assemble) + inner
    return total * 1e-6 / len(rounds)
