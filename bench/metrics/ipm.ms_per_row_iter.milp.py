"""Wall time of the stacked interior point per paid row-iteration, ms:
the summed ``lp.solve_stacked`` spans of the window over their summed
``paid_rows`` (batch width times the slowest active row's iterations,
or what the chunked driver paid)."""
from bench import readers


def read(obs):
    solves = [s for s in readers.spans(obs, "lp.solve_stacked")
              if s.attrs and "paid_rows" in s.attrs]
    paid = sum(s.attrs["paid_rows"] for s in solves)
    if not paid:
        return None
    return sum(s.dur_ns for s in solves) * 1e-6 / paid
