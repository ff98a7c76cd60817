"""Reductions shared by the per-layer metric readers in ``metrics/``.

Each reader gets one :class:`bench.run.Observed` of a traced window and
returns a number, or ``None`` when the window holds nothing to read.
"""
from __future__ import annotations

import numpy as np


def spans(obs, name: str) -> list:
    return [s for s in obs.spans if s.name == name]


def span_seconds(obs, name: str) -> list:
    return [s.dur_ns * 1e-9 for s in spans(obs, name)]


def iters_per_row(obs):
    """Interior-point iterations per active row, from the Newton ledger's
    per-row histogram."""
    its = obs.hists.get("lp.newton.iters") or []
    return float(np.mean(its)) if its else None


def idle_pct(obs):
    t = obs.trace
    if t.window_s <= 0 or not t.busy:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
