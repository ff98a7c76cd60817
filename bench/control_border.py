#!/usr/bin/env python3
"""``bench/control.py`` for the exact trade-off on a large book, whose
node LPs the dense control cannot hold (a 4,129-square normal matrix
formed from a 4,129 x 65,586 matrix, every Newton step):

    python bench/control_border.py --workload portfolio.milp \\
        --seeds 1,2,3 --seconds <s> --control 1 [--limit <n>]

The same readings, options and output as ``bench/control.py``; with
``--control 1`` the control is the plain interior point solved through
the border of its rows (``reference/lp_border.py``) in float32 on the
window's relaxation and node rows, and the window's own allocations at
the checked budgets, rounded to float32 and evaluated in float32 (at
4,096 tasks HiGHS finds no incumbent of Eq. 4 within the mix's time
limit, so there is none of its own to round).  The benchmark's own runs
never run this.
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import control  # noqa: E402

F32 = np.float32


def milp_border_control(st, raw, seed, limit: int) -> dict:
    from bench import data
    from bench.drivers import milp as driver
    from bench.reference import lp as ref_lp
    from bench.reference import lp_border
    from bench.reference import milp as ref_milp
    gaps = {}
    for name, rows in (
            ("relax_gap", [(k, dict(cap=c)) for k, c, _ in
                           driver.relaxation_rows(st)]),
            ("node_gap", [(k, fix) for k, fix, _, _ in
                          driver.node_rows(st)])):
        gap = resid = 0.0
        for k, fix in rows[:limit]:
            lp = ref_lp.build(st.models[k], **fix)
            ref, _ = ref_lp.solve_highs(lp)
            obj, x = lp_border.solve_ipm(lp, F32)
            gap = max(gap, driver.relative_gap(obj, ref))
            resid = max(resid, ref_lp.residual(lp, x)
                        if np.isfinite(x).all() else np.inf)
        gaps[name] = gap
        if name == "node_gap":
            gaps["node_resid"] = resid
        gaps[name + "_compared"] = min(limit, len(rows))
    points = [(s["tenant"], p) for s in raw["sweeps"] for p in s["points"]]
    pick = data.rng(seed, data.SAMPLE).choice(
        len(points), size=min(int(st.mix["highs_points"]), len(points)),
        replace=False)
    gap = 0.0
    for i in sorted(pick):
        k, p = points[i]
        m = st.models[k]
        a32 = np.asarray(p["alloc"]).astype(F32)
        mk32, cost32 = ref_milp.evaluate(m, a32, F32)
        gap = max(gap, ref_milp.allocation_gap(m, p["cap"], a32, mk32,
                                               cost32))
    return dict(gaps, alloc_gap=gap, alloc_compared=int(len(pick)))


control.CONTROLS["milp_large"] = milp_border_control

if __name__ == "__main__":
    sys.exit(control.main())
