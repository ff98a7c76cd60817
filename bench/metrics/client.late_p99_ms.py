"""How late the load generator sent requests: the 99th percentile of
send time minus due time, ms."""
import numpy as np


def read(obs):
    late = obs.raw.get("late_s")
    return None if late is None else float(np.percentile(late, 99) * 1e3)
