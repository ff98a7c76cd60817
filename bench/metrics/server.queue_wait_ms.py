"""Mean wait of a request in the server's queue before its dispatch
began, ms (the program's own ``AllocResult.queue_wait_s``)."""
import numpy as np


def read(obs):
    waits = [r.queue_wait_s for r in obs.raw.get("results") or () if r]
    return float(np.mean(waits) * 1e3) if waits else None
