"""Bytes the stacked solves copied to the device per node-LP row, KB:
the ``lp.put_bytes`` counter over ``lp.put_rows`` (every row of every
stacked node solve of the window, padding included), / 1024."""


def read(obs):
    rows = obs.counters.get("lp.put_rows", 0)
    if not rows:
        return None
    return obs.counters.get("lp.put_bytes", 0) / rows / 1024.0
