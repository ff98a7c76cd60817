#!/usr/bin/env python3
"""Smoke check of the allocation system on a TPU.

Drives the main path once, in one process, through the package's public
entry points, at the paper's full size: 128 Monte Carlo option-pricing
tasks on the 16 Table II platforms (node LPs of 161 rows x 2065
variables), with fitted models from simulated benchmarks.

1. kernels — the stacked relaxation through the Pallas Cholesky kernel
   in float32 (``linsolve="pallas", newton_dtype="float32"``) against the
   default backend, and the Monte Carlo pricing kernel against its jnp
   reference; both kernels must appear compiled (``tpu_custom_call``);
2. served path — ``AllocationServer(ladder_max=32)``: warmup, tenants
   served by the scheduler thread over several ladder widths, every row
   converged, zero recompiles after warmup, sampled frontier points equal
   to HiGHS;
3. lockstep B&B — ``pareto.milp_tradeoff_batched`` over an 8-point budget
   sweep, cut by node limit only, consistent with HiGHS's MILP bounds;
4. fused market replay — 256 megadiverse spot-market episodes in one
   vmapped device program, a few checked against the Python event loop.

Informational lines (set-up and latency seconds) go to stdout as the
phases run; the LAST line is one JSON object, ``{"ok": true, "device":
{...}}``.  Any failed check raises, so the script exits non-zero and
prints no result; it also refuses to run without a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # only the row-sharded paths, 4 chips

``--four-chips`` runs the paths that span chips and what they are
compared with, nothing else: a 4 x 32-row stacked solve sharded over a
solver mesh against the same rows solved unsharded 32 at a time, and a
sharded fused replay against the unsharded one.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# paper deployment: 128 tasks sized at 2e8 paths on the 16-platform cluster
N_TASKS = 128
N_PATHS = int(2e8)
LADDER_MAX = 32          # memory_analysis: ~4 GB of temporaries at width 32
N_CAPS = 8               # budget points per tenant request
N_TENANTS = 6
BNB_NODE_LIMIT = 16      # per budget tree; the only cut of the B&B sweep
N_EPISODES = 256
EPISODE_KW = dict(n_initial=8, max_platforms=16)

HIGHS_RTOL = 1e-6        # served LP frontier vs HiGHS, relative
KERNEL_RTOL = 1e-6       # f32 Pallas Newton path vs default, relative
LOOP_RTOL = 1e-8         # fused replay vs Python loop, relative
SHARD_RTOL = 1e-8        # sharded vs unsharded, relative
# Mosaic's exp/log/sin/cos/sqrt may differ from XLA's in the last ulps;
# over up to 256 GBM steps that moves a path's payoff by ~1e-5 relative
# and can flip a barrier knock-out that sits on the barrier, so the
# kernel and the jnp reference agree statistically-tightly, not bitwise
MC_RTOL = 1e-3


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def rel(a, b) -> float:
    a, b = np.asarray(a, float), np.asarray(b, float)
    return float(np.max(np.abs(a - b) / np.maximum(
        np.maximum(np.abs(a), np.abs(b)), 1e-12)))


@contextlib.contextmanager
def phase(name: str):
    print(f"[{name}] start", flush=True)
    t0 = time.perf_counter()
    yield
    print(f"[{name}] done in {time.perf_counter() - t0:.3f} s", flush=True)


def tpu_device():
    """The first TPU, or exit non-zero naming what JAX found instead."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found — JAX's devices are "
                 f"{devs[0].platform!r} ({len(devs)}); this check needs "
                 f"a TPU")
    return devs


# ---------------------------------------------------------------------------
# Deployment
# ---------------------------------------------------------------------------

def paper_tenants(n_tenants: int, n_tasks: int = N_TASKS, platforms=None):
    """``n_tenants`` fitted paper deployments: the same 128 tasks and
    16 platforms, each fitted from its own simulated benchmark noise."""
    from repro.core import iaas
    from repro.pricing import simulate
    from repro.pricing.tasks import generate_tasks
    platforms = iaas.paper_platforms() if platforms is None else platforms
    tasks = [t.with_paths(N_PATHS) for t in generate_tasks(n_tasks)]
    return tasks, [simulate.fit_problem(tasks, platforms, seed=11 + k)[0]
                   for k in range(n_tenants)]


def budget_sweep(problem, n_caps: int) -> np.ndarray:
    """Cheapest single platform up to the cost of the latency-weighted
    proportional split."""
    from repro.core import heuristics
    c_l = float(problem.single_platform_cost().min())
    w = 1.0 / problem.single_platform_latency()
    _, c_u = heuristics.evaluate(problem,
                                 heuristics.proportional_split(problem, w))
    return np.linspace(c_l, max(float(c_u), 1.5 * c_l), n_caps)


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def kernels_phase(problem, tasks, *, n_blocks: int = 16) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import lp, pareto
    from repro.kernels import ops
    from repro.pricing.options import KIND_IDS

    # Newton kernel: the stacked relaxation through the f32 Pallas path
    nodes = pareto.frontier_nodes(problem, budget_sweep(problem, N_CAPS))
    base = lp.solve_node_lps_stacked(nodes)
    t0 = time.perf_counter()
    pal = lp.solve_node_lps_stacked(nodes, linsolve="pallas",
                                    newton_dtype="float32")
    print(f"  pallas f32 stacked solve, first call (with compile): "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    check(bool(np.asarray(base.converged).all()), "default solve diverged")
    check(bool(np.asarray(pal.converged).all()),
          "pallas f32 solve has unconverged rows")
    d = rel(pal.obj, base.obj)
    print(f"  pallas f32 vs default objective rel diff {d:.3e}", flush=True)
    check(d <= KERNEL_RTOL, f"pallas f32 objectives off by {d:.3e}")
    stacked = [jnp.asarray(np.stack([np.asarray(getattr(n, f))
                                     for n in nodes]), jnp.float64)
               for f in ("c", "a_eq", "b_eq", "g", "h", "lb", "ub")]
    solver = lp._stacked_solver((0,) * 7, lp._MAX_ITERS, "pallas",
                                "float32")
    hlo = solver.lower(jnp.asarray(lp._TOL, jnp.float64),
                       jnp.ones((len(nodes),), bool), *stacked
                       ).compile().as_text()
    check("tpu_custom_call" in hlo,
          "the stacked Pallas solve did not compile the Cholesky kernel")

    # Monte Carlo pricing kernel vs its jnp reference, per option kind
    groups: dict = {}
    for t in tasks:
        groups.setdefault((t.kind, t.steps), []).append(t)
    n_paths = n_blocks * 1024
    worst = 0.0
    for (kind, steps), group in sorted(groups.items()):
        params = jnp.asarray(np.stack([t.with_paths(n_paths).param_row()
                                       for t in group]))
        kw = dict(kind_id=KIND_IDS[kind], steps=steps, n_blocks=n_blocks)
        mean_k, se_k = ops.mc_price(params, use_pallas=True, **kw)
        mean_r, se_r = ops.mc_price(params, use_pallas=False, **kw)
        d = max(rel(mean_k, mean_r), rel(se_k, se_r))
        worst = max(worst, d)
        check(d <= MC_RTOL, f"MC kernel {kind}/{steps} off by {d:.3e}")
        hlo = jax.jit(lambda p: ops.mc_price(p, use_pallas=True, **kw)
                      ).lower(params).compile().as_text()
        check("tpu_custom_call" in hlo,
              f"MC kernel {kind}/{steps} was not compiled for the chip")
    print(f"  MC kernel vs reference: {len(groups)} (kind, steps) groups, "
          f"worst rel diff {worst:.3e}", flush=True)


def served_phase(tenants, *, ladder_max: int = LADDER_MAX,
                 n_caps: int = N_CAPS) -> None:
    from repro.core import lp, pareto
    from repro.serving import AllocationServer, AllocRequest

    srv = AllocationServer(ladder_max=ladder_max)
    t0 = time.perf_counter()
    widths = srv.warmup(tenants[0])
    print(f"  warmup (compile) of ladder widths {widths}: "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    reqs = [AllocRequest(f"tenant{k}", p, budget_sweep(p, n_caps))
            for k, p in enumerate(tenants)]
    # queue all but one before the scheduler starts: its first dispatch
    # then coalesces a full ladder and the rest follow at narrower widths
    futs = [srv.submit(r) for r in reqs[:-1]]
    srv.start()
    try:
        results = [f.result(timeout=900) for f in futs]
        results.append(srv.request(reqs[-1], timeout=900))
    finally:
        srv.stop()
    st = srv.stats()
    print(f"  served {st['requests']} requests in {st['dispatches']} "
          f"dispatches, widths {st['widths_used']}, latency p50 "
          f"{st['p50_ms']:.3f} ms p99 {st['p99_ms']:.3f} ms", flush=True)
    check(len(st["widths_used"]) > 1,
          f"dispatches used one ladder width only: {st['widths_used']}")
    check(st["recompiles_since_warmup"] == 0,
          f"{st['recompiles_since_warmup']} recompiles after warmup")
    for res in results:
        check(bool(np.asarray(res.frontier.converged).all()),
              f"{res.tenant}: unconverged frontier rows")
    worst = 0.0
    for res, req in ((results[0], reqs[0]), (results[-1], reqs[-1])):
        for j in (0, n_caps // 2, n_caps - 1):
            node = pareto.frontier_nodes(req.problem, req.caps[j:j + 1])[0]
            ref = lp.scipy_reference_lp(node.c, node.a_eq, node.b_eq,
                                        node.g, node.h, node.lb, node.ub)
            check(ref.success, f"HiGHS failed on {req.tenant} cap {j}")
            worst = max(worst, rel(res.frontier.makespans[j], ref.fun))
    print(f"  frontier vs HiGHS: worst rel diff {worst:.3e}", flush=True)
    check(worst <= HIGHS_RTOL, f"frontier off HiGHS by {worst:.3e}")


def bnb_phase(problem, *, n_points: int = N_CAPS,
              node_limit: int = BNB_NODE_LIMIT, n_highs: int = 3,
              highs_time_limit_s: float = 20.0) -> None:
    from repro import obs
    from repro.core import milp, pareto

    gap_tol = 1e-4                 # solve_bnb_sweep's and HiGHS's default
    before = obs.read_counter("milp.host_resolves")
    t0 = time.perf_counter()
    front = pareto.milp_tradeoff_batched(problem, n_points=n_points,
                                         node_limit=node_limit,
                                         time_limit_s=float("inf"),
                                         gap_tol=gap_tol)
    wall = time.perf_counter() - t0
    host = int(obs.read_counter("milp.host_resolves") - before)
    pts = [p for p in front.points if p.cost_cap is not None]
    status = [p.meta["status"] for p in pts]
    print(f"  B&B sweep: {len(pts)} points in {wall:.3f} s, statuses "
          f"{status}, nodes {[p.meta['nodes'] for p in pts]}, "
          f"node LPs re-solved by HiGHS on the host: {host}", flush=True)
    check(len(pts) == n_points, f"only {len(pts)} of {n_points} budgets "
          f"found an allocation")
    for p in pts:
        check(p.cost <= p.cost_cap * (1 + 1e-6),
              f"allocation over budget at cap {p.cost_cap}")
    for k in np.linspace(0, len(pts) - 1, n_highs).astype(int):
        p = pts[int(k)]
        h = milp.solve(problem, p.cost_cap, backend="highs",
                       time_limit_s=highs_time_limit_s)
        print(f"  cap {p.cost_cap:.4f}: B&B {p.meta['status']} makespan "
              f"{p.makespan:.6f} bound {p.meta['lb']:.6f}; HiGHS "
              f"{h.status} makespan {h.makespan:.6f} bound "
              f"{h.lower_bound:.6f}", flush=True)
        # each side's bound holds the other side's incumbent
        check(p.meta["lb"] <= h.makespan * (1 + gap_tol),
              f"B&B bound above HiGHS's allocation at cap {p.cost_cap}")
        check(h.lower_bound <= p.makespan * (1 + gap_tol),
              f"HiGHS bound above the B&B allocation at cap {p.cost_cap}")
        if p.meta["status"] == "optimal" and h.status == "optimal":
            check(abs(p.makespan - h.makespan)
                  <= 2 * gap_tol * max(p.makespan, h.makespan),
                  f"optimal makespans disagree at cap {p.cost_cap}")


def market_inputs(problem, n_episodes: int, seed: int = 0):
    """Megadiverse episodes on the fleet of ``problem``'s platforms, with
    per-episode SLOs and the resplit policy's t=0 plans."""
    from repro.market import events, simulator
    from repro.market.policies import ResplitPolicy
    catalog = simulator.catalog_from_problem(problem)
    eps = events.megadiverse_episodes([k.name for k in catalog],
                                      n_episodes=n_episodes, seed=seed,
                                      **EPISODE_KW)
    pol = ResplitPolicy()
    slos, alloc0s = [], []
    for ep in eps:
        fleet = simulator.Fleet.from_episode(catalog, problem.n, ep)
        lat = fleet.problem().single_platform_latency()
        slo = float(lat[~fleet.dead].min()) * 0.8
        slos.append(slo)
        alloc0s.append(pol.reset(fleet.view(0.0, slo)))
    return catalog, eps, slos, alloc0s


def fused_phase(problem, *, n_episodes: int = N_EPISODES,
                n_loop: int = 3) -> None:
    from repro.market import events, fused, metrics, simulator
    from repro.market.policies import ResplitPolicy

    catalog, eps, slos, alloc0s = market_inputs(problem, n_episodes)
    tensors = events.stack_event_tensors(eps)
    kw = dict(policy_kind="resplit", slo_latencies=slos, alloc0s=alloc0s,
              tensors=tensors)
    t0 = time.perf_counter()
    out = fused.run_episodes_vmapped(catalog, problem.n, eps, **kw)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    fused.run_episodes_vmapped(catalog, problem.n, eps, **kw)
    warm = time.perf_counter() - t0
    print(f"  {len(eps)} episodes ({tensors[0].time.shape[0]} event "
          f"slots): first call (with compile) {cold:.3f} s, second "
          f"{warm:.3f} s", flush=True)
    worst = 0.0
    for i in range(n_loop):
        loop = metrics.summarise(simulator.run_episode(
            catalog, problem.n, eps[i], ResplitPolicy(),
            slo_latency=slos[i]))
        got = out[i]
        worst = max(worst, rel(got.accrued_cost, loop.accrued_cost),
                    rel(got.avg_makespan, loop.avg_makespan),
                    rel(got.slo_violation_s, loop.slo_violation_s))
        check(got.slo_violations == loop.slo_violations
              and got.replans == loop.replans,
              f"episode {i}: violation/replan counts differ from the loop")
    print(f"  fused vs Python loop on {n_loop} episodes: worst rel diff "
          f"{worst:.3e}", flush=True)
    check(worst <= LOOP_RTOL, f"fused replay off the loop by {worst:.3e}")


def four_chip_phase(problem, *, rows_per_shard: int = 32,
                    n_episodes: int = N_EPISODES) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import lp, pareto
    from repro.launch.mesh import make_solver_mesh
    from repro.market import events, fused

    mesh = make_solver_mesh()
    n_dev = mesh.devices.size
    check(n_dev == 4, f"--four-chips needs 4 devices, the mesh has {n_dev}")
    n_rows = n_dev * rows_per_shard
    nodes = pareto.frontier_nodes(problem, budget_sweep(problem, n_rows))

    t0 = time.perf_counter()
    sharded = lp.solve_node_lps_stacked(nodes, mesh=mesh)
    print(f"  sharded stacked solve of {n_rows} rows (with compile): "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    spans = len(sharded.x.sharding.device_set)
    check(spans == n_dev, f"sharded solve output on {spans} device(s)")
    t0 = time.perf_counter()
    parts = [lp.solve_node_lps_stacked(nodes[i:i + rows_per_shard])
             for i in range(0, n_rows, rows_per_shard)]
    print(f"  unsharded solves, {rows_per_shard} rows at a time (with "
          f"compile): {time.perf_counter() - t0:.3f} s", flush=True)
    conv = np.concatenate([np.asarray(p.converged) for p in parts])
    check(bool(conv.all()) and bool(np.asarray(sharded.converged).all()),
          "unconverged rows in the 4-chip comparison")
    d_obj = rel(sharded.obj, np.concatenate([np.asarray(p.obj)
                                             for p in parts]))
    d_x = float(np.abs(np.asarray(sharded.x) - np.concatenate(
        [np.asarray(p.x) for p in parts])).max())
    print(f"  stacked solve, sharded vs unsharded: objective rel diff "
          f"{d_obj:.3e}, max |x| diff {d_x:.3e}, output on {spans} "
          f"devices", flush=True)
    check(d_obj <= SHARD_RTOL, f"sharded objectives off by {d_obj:.3e}")

    catalog, eps, slos, alloc0s = market_inputs(problem, n_episodes)
    tensors = events.stack_event_tensors(eps)
    kw = dict(policy_kind="resplit", slo_latencies=slos, alloc0s=alloc0s,
              tensors=tensors)
    t0 = time.perf_counter()
    shard = fused.run_episodes_vmapped(catalog, problem.n, eps, mesh=mesh,
                                       **kw)
    print(f"  sharded fused replay of {len(eps)} episodes (with compile): "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    one = fused.run_episodes_vmapped(catalog, problem.n, eps, **kw)
    worst = 0.0
    for a, b in zip(shard, one):
        worst = max(worst, rel(a.accrued_cost, b.accrued_cost),
                    rel(a.avg_makespan, b.avg_makespan),
                    rel(a.slo_violation_s, b.slo_violation_s))
        check(a.replans == b.replans, "sharded replay replans differ")
    # where the sharded replay's output lives: run the cached program
    # once more on the same inputs and read its output placement
    key = ("episode-vmap", "resplit", 9, lp._mesh_key_of(mesh, ("lp_rows",)))
    program = fused._FUSED_REPLAYS[key]
    batched = ([np.asarray(slos), np.array([t.horizon_s for t in tensors])]
               + [np.stack([getattr(t, f) for t in tensors])
                  for f in ("time", "kind_id", "slot", "kind_index",
                            "scale", "init_occupied", "init_kind")]
               + [np.stack(alloc0s)])
    res = program(*fused.fused_catalog(catalog, problem.n),
                  *(jnp.asarray(v) for v in batched))
    spans = len(res[0].sharding.device_set)
    jax.block_until_ready(res)
    print(f"  fused replay, sharded vs unsharded: worst rel diff "
          f"{worst:.3e}, output on {spans} devices", flush=True)
    check(worst <= SHARD_RTOL, f"sharded replay off by {worst:.3e}")
    check(spans == n_dev, f"sharded replay output on {spans} device(s)")


# ---------------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the paths sharded over 4 chips and "
                         "their unsharded comparison")
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import compile_cache
    cache_dir = compile_cache.enable()
    devs = tpu_device()
    print(f"device: {devs[0].device_kind}, count {len(devs)}; compile "
          f"cache {cache_dir}", flush=True)

    t_start = time.perf_counter()
    with phase("deployment"):
        tasks, tenants = paper_tenants(1 if args.four_chips else N_TENANTS)
        print(f"  {tenants[0].tau} tasks x {tenants[0].mu} platforms, "
              f"{len(tenants)} fitted tenant(s)", flush=True)
    if args.four_chips:
        with phase("four-chips"):
            four_chip_phase(tenants[0])
    else:
        with phase("kernels"):
            kernels_phase(tenants[0], tasks)
        with phase("served"):
            served_phase(tenants)
        with phase("bnb"):
            bnb_phase(tenants[0])
        with phase("fused"):
            fused_phase(tenants[0])
    print(f"total {time.perf_counter() - t_start:.3f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
