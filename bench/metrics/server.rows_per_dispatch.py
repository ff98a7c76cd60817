"""Mean live LP rows per dispatch (the program's ``DispatchRecord``s)."""
import numpy as np


def read(obs):
    recs = obs.raw.get("dispatches") or ()
    return float(np.mean([d.n_rows for d in recs])) if recs else None
