"""The structured node-LP interior point's share of the HBM roofline, %:
the bytes its paid row-iterations must move at least
(``bench/nodelp_bytes.py``, from each solve's ``mu`` and ``tau``) over
what the chip's HBM moves (``bench/peaks.json``) in the device time of
its programs' ops (modules ``jit_node_ipm*``).

Only the node solves of the traced slice count: the ``lp.solve_stacked``
spans between the run's start and stop of the profiler, paired in order
with their annotations in the trace, and only those that end before the
device record does (the profiler keeps a fixed number of device events);
a solve's device time is that of the node-IPM ops that start inside it.
"""
import json
from pathlib import Path

from bench import nodelp_bytes, readers

PROGRAM = "jit_node_ipm"
PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


def _hbm_bytes_per_s():
    import jax
    table = json.loads(PEAKS.read_text())
    return table.get(jax.devices()[0].device_kind, {}).get("hbm_bytes_per_s")


def _slice(obs):
    """(start, end) ns, host clock, of the profiled slice."""
    own = sorted(obs.profiler_ns)
    if not own:
        return None
    return own[0][1], own[1][0] if len(own) > 1 else float("inf")


def read(obs):
    t = obs.trace
    bounds = _slice(obs)
    if t is None or bounds is None:
        return None
    a, b = bounds
    solves = sorted((s for s in readers.spans(obs, "lp.solve_stacked")
                     if a <= s.ts_ns and s.ts_ns + s.dur_ns <= b),
                    key=lambda s: s.ts_ns)
    marks = sorted(t.host.get("lp.solve_stacked") or [])
    if not solves or len(marks) != len(solves):
        return None
    need, kept = 0, []
    for s, (m0, m1, _) in zip(solves, marks):
        at = s.attrs or {}
        if "tau" not in at or "paid_rows" not in at or m1 > t.window[1]:
            continue
        need += at["paid_rows"] * nodelp_bytes.per_row_iteration(
            at["mu"], at["tau"])
        kept.append((m0, m1))
    dev_ns = sum(op.self_ns for op in t.ops if op.module.startswith(PROGRAM)
                 and any(m0 <= op.start < m1 for m0, m1 in kept))
    peak = _hbm_bytes_per_s()
    if not need or not dev_ns or not peak:
        return None
    return 100.0 * need / (dev_ns * 1e-9 * peak)
