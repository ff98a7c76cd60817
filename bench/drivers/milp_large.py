"""Exact trade-off sweeps on a large book of options: the ``milp``
driver's sweeps, window, check and release, behind a set-up guard.

Before any solve, the guard builds one tenant's root node LP and refuses
the run when the arrays the node object stores pass ``LIMIT_BYTES``.  A
program that builds its node LPs densely holds a ``tau x n`` equality
matrix (2.15 GB at 4,096 tasks on 16 platforms), and would grind through
dense Newton steps for many minutes before running out of memory; it
fails here at once instead.
"""
from __future__ import annotations

import numpy as np

from bench import data
from bench.drivers import milp
from bench.drivers.milp import (State, check, end_to_end, release,  # noqa: F401
                                trace_slice, window)

LIMIT_BYTES = 1e9


def held_bytes(node) -> int:
    """Bytes of the arrays a node object stores: its tuple fields and
    instance attributes.  Properties, which may build arrays on read,
    are not called."""
    values = list(node) if isinstance(node, tuple) else []
    values += list(getattr(node, "__dict__", {}).values())
    return sum(v.nbytes for v in values if isinstance(v, np.ndarray))


def guard(node, limit=None) -> None:
    """Refuse the run (exit non-zero) when ``node`` stores more than
    ``limit`` bytes of arrays (default ``LIMIT_BYTES``)."""
    limit = LIMIT_BYTES if limit is None else limit
    held = held_bytes(node)
    if held > limit:
        raise SystemExit(
            f"bench/drivers/milp_large.py: one node LP of this deployment "
            f"holds {held / 1e9:.2f} GB of arrays, over the "
            f"{limit / 1e9:.2f} GB this cell allows: the program builds "
            f"a dense node LP, which this deployment cannot run")


def setup(cfg: dict, mix: dict, seed: int, seconds: float, *, root=None,
          log=print) -> State:
    from repro.core.problem import AllocationProblem
    m = data.tenant_models(cfg, seed)[0]
    problem = AllocationProblem(m["beta"], m["gamma"], m["n"], m["rho"],
                                m["pi"], m["names"])
    guard(problem.node_lp(None))
    return milp.setup(cfg, mix, seed, seconds, root=root, log=log)
