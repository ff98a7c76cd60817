#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>
                            [--control 1] [--fault <fault>] [--mix <json>]

For each seed, in one process (so set-up is paid once per shape): make
the cell's inputs, run a window at the cell's own load, and print the
numbers the run's check compares (the program's readings).  With
``--control 1`` it also prints the same numbers with the lower-precision
control in the program's place: the plain reference computed one
precision down (float32 for the float64 the configurations state):

- served replans: the relaxation solved by the plain interior point of
  ``reference/lp.py`` in float32;
- exact trade-off: the plain interior point of ``reference/lp.py`` in
  float32 on the window's relaxation and node rows, and HiGHS's
  incumbent allocation at each checked budget, rounded to float32 and
  evaluated in float32;
- regret sweep: the plain episode loop of ``reference/episodes.py`` in
  float32.

``--fault`` plants one of ``bench/faults.py``'s faults in the program
first, so the readings are the fault's; ``--mix`` overrides traffic
parameters, such as the program's own float32 Newton path.  One JSON
line per seed.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]

F32 = np.float32


def served_control(st, raw, seed, limit: int) -> dict:
    from bench import data
    from bench.reference import lp as ref_lp
    gap = resid = 0.0
    done = [k for k, r in enumerate(raw["results"]) if r is not None]
    pick = data.rng(seed, data.SAMPLE).permutation(done)[:limit]
    for k in pick:
        q = st.schedule[k]
        m = st.models[q["tenant"]]
        lp = ref_lp.build(m, q["cap"], q["dead"])
        ref, _ = ref_lp.solve_highs(lp)
        obj, x = ref_lp.solve_ipm(lp, F32)
        mu, tau = m["beta"].shape
        gap = max(gap, abs(obj - ref) / ref)
        resid = max(resid, ref_lp.allocation_residual(
            m, q["cap"], q["dead"], x[:mu * tau].reshape(mu, tau), obj))
    return {"frontier_gap": gap, "alloc_resid": resid,
            "compared": int(len(pick))}


def milp_control(st, raw, seed, limit: int) -> dict:
    from bench import data
    from bench.drivers import milp as driver
    from bench.reference import lp as ref_lp
    from bench.reference import milp as ref_milp
    mix = st.mix
    # the stacked interior point's answers: the plain interior point in
    # float32 on the same relaxations and node relaxations
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
            obj, x = ref_lp.solve_ipm(lp, F32)
            gap = max(gap, driver.relative_gap(obj, ref))
            resid = max(resid, ref_lp.residual(lp, x)
                        if np.isfinite(x).all() else np.inf)
        gaps[name] = gap
        if name == "node_gap":
            gaps["node_resid"] = resid
        gaps[name + "_compared"] = min(limit, len(rows))
    # the answers: HiGHS's incumbent at each checked budget, rounded to
    # float32 and evaluated in float32
    points = [(s["tenant"], p) for s in raw["sweeps"] for p in s["points"]]
    pick = data.rng(seed, data.SAMPLE).choice(
        len(points), size=min(int(mix["highs_points"]), len(points)),
        replace=False)
    gap = 0.0
    for i in sorted(pick):
        k, p = points[i]
        m = st.models[k]
        _, _, alloc = ref_milp.solve_highs(
            m, p["cap"], float(mix["highs_time_limit_s"]),
            float(mix["gap_tol"]))
        if alloc is None:
            continue
        a32 = np.asarray(alloc).astype(F32)
        mk32, cost32 = ref_milp.evaluate(m, a32, F32)
        gap = max(gap, ref_milp.allocation_gap(m, p["cap"], a32, mk32,
                                               cost32))
    return dict(gaps, alloc_gap=gap, alloc_compared=int(len(pick)))


def regret_control(st, raw, seed, limit: int) -> dict:
    from bench.reference import episodes as ref_ep
    from bench.drivers import regret
    gap = 0.0
    for i in st.sample[:limit]:
        ref = regret.reference_totals(st, int(i))
        low = regret.reference_totals(st, int(i), F32)
        gap = max(gap, ref_ep.totals_gap(low, ref, st.eps[i]["horizon_s"]))
    return {"episode_gap": gap, "compared": int(min(limit,
                                                     len(st.sample)))}


CONTROLS = {"served": served_control, "milp": milp_control,
            "regret": regret_control}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--limit", type=int, default=10**9,
                    help="most answers the control is read on per seed")
    ap.add_argument("--fault", default="",
                    help="plant this fault of bench/faults.py first")
    ap.add_argument("--mix", default="{}",
                    help="JSON of traffic parameters to override, such as "
                    '\'{"newton_dtype": "float32"}\' (a path of the program)')
    args = ap.parse_args()
    for p in (ROOT / "src", ROOT):
        sys.path.insert(0, str(p))
    from bench import faults, run
    cell = run.find_cell(ROOT, args.workload)
    cell.mix.update(json.loads(args.mix))
    if args.fault:
        faults.FAULTS[(cell.mix["driver"], args.fault)](faults.Patch())
    run.enable_compile_cache(ROOT)
    run.chips_or_exit(cell.chips)
    driver = run.load_driver(cell.mix["driver"])
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        st = driver.setup(cell.config, cell.mix, seed, args.seconds,
                          root=ROOT, log=log)
        raw = driver.window(st, args.seconds)
        driver.release(st)
        out = {"seed": seed, "attempted": raw["attempted"],
               "failed": raw["failed"],
               "program": {k: v["value"] for k, v in
                           driver.check(st, raw, seed, log=log).items()}}
        if args.control:
            out["control"] = CONTROLS[cell.mix["driver"]](st, raw, seed,
                                                          args.limit)
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
