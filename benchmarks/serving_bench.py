"""Allocation-as-a-Service serving benchmark (beyond-paper subsystem).

Drives the continuous-batching :class:`repro.serving.AllocationServer`
with a multi-tenant open-loop workload and reports:

* ``serving.warmup`` — AOT ladder warm cost (one all-retired compile
  per width) and the number of widths compiled;
* ``serving.p50`` / ``serving.p99`` — request latency percentiles over
  the sustained phase (submit -> future resolution, microseconds);
* ``serving.rps`` — sustained requests/second through the scheduler;
* ``serving.coalesce`` — mean dispatched-batch occupancy and how many
  requests shared each stacked call;
* ``serving.steady_state`` — ZERO stacked-solver recompiles after
  warmup, asserted (CI fails on a recompile), plus the per-tenant
  parity check: frontiers sliced from coalesced dispatches match solo
  solves to <= 1e-8 (also asserted).

Standalone:  python -m benchmarks.serving_bench [--smoke] [--seed N]
             [--out f.csv]
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import experiment_problem, seeded, smoke_scaled
from repro.core import lp, pareto
from repro.serving import AllocRequest, AllocationServer


def _tenant_sweeps(problem, n_tenants: int, rng) -> list:
    """One budget sweep per tenant, sizes deliberately MIXED (1..6
    caps) so dispatches exercise several ladder widths."""
    c_l = float(problem.single_platform_cost().min())
    sweeps = []
    for _ in range(n_tenants):
        k = int(rng.integers(1, 7))
        lo, hi = rng.uniform(1.0, 1.5), rng.uniform(2.0, 4.0)
        sweeps.append(np.linspace(lo * c_l, hi * c_l, k))
    return sweeps


def run() -> list:
    rows = []
    rng = np.random.default_rng(seeded(17))
    fitted, *_ = experiment_problem(smoke_scaled(16, 8),
                                    smoke_scaled(8, 4), seed=9)
    ladder_max = smoke_scaled(32, 16)
    srv = AllocationServer(ladder_max=ladder_max)

    # -- cold start: AOT-warm the whole width ladder ---------------------
    t0 = time.perf_counter()
    widths = srv.warmup(fitted)
    warm_s = time.perf_counter() - t0
    compiles_after_warm = lp.stacked_compile_count()
    rows.append(("serving.warmup", warm_s * 1e6,
                 f"widths={len(widths)};ladder_max={ladder_max};"
                 f"us_per_width={warm_s * 1e6 / len(widths):.0f}"))

    # -- parity: coalesced vs solo frontiers (acceptance <= 1e-8) --------
    par_caps = _tenant_sweeps(fitted, 3, rng)
    futs = [srv.submit(AllocRequest(f"par{i}", fitted, caps))
            for i, caps in enumerate(par_caps)]
    srv.run_until_idle()
    max_diff = 0.0
    for caps, fut in zip(par_caps, futs):
        solo = lp.solve_node_lps_stacked(pareto.frontier_nodes(fitted, caps))
        merged = fut.result(timeout=0).frontier.makespans
        denom = 1.0 + np.abs(np.asarray(solo.obj))
        max_diff = max(max_diff, float(
            (np.abs(merged - np.asarray(solo.obj)) / denom).max()))
    assert max_diff <= 1e-8, \
        f"coalesced frontier drifted {max_diff:.2e} from solo solves"
    # the solo reference solves above may compile their own (non-ladder)
    # widths; re-anchor the steady-state baseline after them
    baseline = lp.stacked_compile_count()

    # -- sustained multi-tenant phase ------------------------------------
    n_waves = smoke_scaled(12, 4)
    n_tenants = smoke_scaled(8, 4)
    served = 0
    t0 = time.perf_counter()
    lat_mark = srv.total_requests
    for _ in range(n_waves):
        sweeps = _tenant_sweeps(fitted, n_tenants, rng)
        for i, caps in enumerate(sweeps):
            srv.submit(AllocRequest(f"t{i}", fitted, caps,
                                    priority=int(rng.integers(0, 3))))
        served += srv.run_until_idle()
    wall = time.perf_counter() - t0
    # latencies_s is a bounded deque; take this phase's tail (the phase
    # fits inside the window for every bench size)
    n_phase = min(srv.total_requests - lat_mark, len(srv.latencies_s))
    lat = np.asarray(list(srv.latencies_s)[-n_phase:]) * 1e6  # us
    p50, p99 = np.percentile(lat, 50), np.percentile(lat, 99)
    rps = served / wall
    occ = np.mean([d.occupancy for d in srv.dispatches])
    per_disp = served / max(len(srv.dispatches), 1)
    rows.append(("serving.p50", float(p50),
                 f"requests={served};waves={n_waves}"))
    rows.append(("serving.p99", float(p99),
                 f"p50_us={p50:.0f};requests={served}"))
    rows.append(("serving.rps", wall * 1e6 / max(served, 1),
                 f"rps={rps:.1f}"))
    rows.append(("serving.coalesce", 0.0,
                 f"mean_occupancy={occ:.2f};"
                 f"requests_per_dispatch={per_disp:.2f};"
                 f"widths_used={'/'.join(map(str, srv.stats()['widths_used']))}"))

    # -- zero-recompile steady state (asserted) --------------------------
    recompiles = lp.stacked_compile_count() - baseline
    assert recompiles == 0, \
        f"stacked solver recompiled {recompiles}x after warmup"
    # per-config attribution: the solo reference solves above may have
    # compiled NON-ladder widths (moving the global count), but zero of
    # those events belong to this server's (shape, config, ladder) key
    assert lp.stacked_compile_count() >= compiles_after_warm
    assert srv.recompiles_since_warmup == 0, \
        f"server attributed {srv.recompiles_since_warmup} recompiles"
    bd = srv.stats()["breakdown"]
    rows.append(("serving.steady_state", 0.0,
                 f"recompiles_after_warmup={recompiles};"
                 f"parity_vs_solo={max_diff:.2e};"
                 f"queue_wait_p99_ms={bd['queue_wait_p99_ms']:.3f};"
                 f"solve_p50_ms={bd['solve_p50_ms']:.1f};ok"))
    return rows


def main() -> None:
    import argparse
    import os

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--seed", type=int, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.smoke:
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    if args.seed is not None:
        os.environ["REPRO_BENCH_SEED"] = str(args.seed)
    from repro import compile_cache
    compile_cache.enable()
    lines = ["name,us_per_call,derived"]
    print(lines[0])
    for name, us, derived in run():
        line = f"{name},{us:.1f},{derived}"
        lines.append(line)
        print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write("\n".join(lines) + "\n")


if __name__ == "__main__":
    main()
