#!/usr/bin/env python3
"""Sweep the offered rate of a served cell to find its knee.

    python bench/sweep.py --workload paper.replans --seed <n> --seconds <s>
                          --rates 2,4,6,8

One process: the cell's set-up once, then one open-loop window per rate
with the cell's traffic at that rate.  One JSON line per rate: requests,
latency p50/p95/max, completed per second, and whether the backlog grew
(the median latency of the window's last third over its first third).
The cell's rate is fixed in its traffic file from such a sweep; the
benchmark's own runs never search for one.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    for p in (ROOT / "src", ROOT):
        sys.path.insert(0, str(p))
    from bench import run
    from bench.drivers import served
    cell = run.find_cell(ROOT, args.workload)
    run.enable_compile_cache(ROOT)
    run.chips_or_exit(cell.chips)
    st = served.setup(cell.config, cell.mix, args.seed, args.seconds,
                      root=ROOT, log=lambda m: print(m, file=sys.stderr))
    for rate in (float(r) for r in args.rates.split(",")):
        plan = served.schedule(st.models, cell.mix, args.seed, args.seconds,
                               rate=rate)
        raw = served.window(st, args.seconds,
                            requests=served.requests_for(st.problems, plan),
                            plan=plan)
        lat = raw["latency_s"]
        third = max(1, len(lat) // 3)
        recs = raw["dispatches"]
        print(json.dumps({
            "rate_per_s": rate, "requests": len(lat), "failed": raw["failed"],
            "p50_s": float(np.percentile(lat, 50)),
            "p95_s": float(np.percentile(lat, 95)),
            "max_s": float(lat.max()),
            "completed_per_s": len(lat) / (raw["t_end"] - raw["t0"]),
            "backlog_growth": float(np.median(lat[-third:])
                                    / np.median(lat[:third])),
            "dispatches": len(recs),
            "mean_rows": float(np.mean([d.n_rows for d in recs])),
            "mean_solve_s": float(np.mean([d.solve_wall_s for d in recs])),
            "widths": sorted({d.width for d in recs})}), flush=True)
        st.server.dispatches.clear()
    return 0


if __name__ == "__main__":
    sys.exit(main())
