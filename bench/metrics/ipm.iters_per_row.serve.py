"""Interior-point iterations per active LP row (Newton ledger)."""
from bench import readers


def read(obs):
    return readers.iters_per_row(obs)
