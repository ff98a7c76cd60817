"""Share of the traced window in which no operation ran on the device, %."""
from bench import readers


def read(obs):
    return readers.idle_pct(obs)
