"""Deployment data made from the run's seed.

The benchmark owns its inputs: the Table II platform numbers and the
task draw are read from the configuration file, the benchmark latencies
are simulated here and the latency models are fitted here by a plain
weighted least squares in numpy.  The program under test receives only
the fitted arrays, so a change to its own pricing or fitting code cannot
move what the benchmark feeds it.

Every draw comes from ``rng(seed, stream...)``: the same seed gives the
same arrays, and separate streams keep one draw from shifting another.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

SECONDS_PER_HOUR = 3600.0
# streams of the seed: one per kind of draw
TASKS, FIT, REQUESTS, EPISODES, SAMPLE = range(5)


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one stream of a run's seed (any whole number)."""
    seed = int(seed)
    return np.random.default_rng(
        np.random.SeedSequence([abs(seed), int(seed < 0), *stream]))


def load_config(root: Path, name: str) -> dict:
    path = Path(root) / "bench" / "configs" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration file {path}")
    cfg = json.loads(path.read_text())
    if cfg.get("name") != name:
        raise ValueError(f"{path} names itself {cfg.get('name')!r}")
    return cfg


def platforms(cfg: dict) -> list:
    """One entry per platform instance, in the configuration's order."""
    out = []
    for group in cfg["platforms"]:
        for k in range(int(group["count"])):
            out.append(dict(group, name=f"{group['name']}-{k}"))
    return out


def task_steps(cfg: dict, r: np.random.Generator) -> np.ndarray:
    """Time steps per path of each task: European payoffs take one step,
    path-dependent ones one of ``steps_choices``; kinds cycle in order."""
    kinds = cfg["task_kinds"]
    steps = np.empty(cfg["n_tasks"], dtype=np.int64)
    for t in range(cfg["n_tasks"]):
        if kinds[t % len(kinds)].startswith("european"):
            steps[t] = 1
        else:
            steps[t] = int(r.choice(cfg["steps_choices"]))
    return steps


def true_models(cfg: dict, steps: np.ndarray):
    """Ground-truth (beta, gamma), each (mu, tau) seconds: a platform's
    rate per path step follows its Table II application GFLOPS."""
    plats = platforms(cfg)
    flops_per_path = cfg["flops_per_path_step"] * steps.astype(np.float64)
    beta = np.stack([flops_per_path
                     / (p["app_gflops"] * 1e9 * cfg["efficiency"][p["kind"]])
                     for p in plats])
    gamma = np.stack([p["setup_s"] + cfg["setup_per_64_steps_s"]
                      * steps.astype(np.float64) / 64.0 for p in plats])
    return beta, gamma


def wls(n: np.ndarray, lat: np.ndarray, w: np.ndarray):
    """Weighted least squares of ``lat = beta * n + gamma`` along the last
    axis, clipped to beta >= 1e-12 and gamma >= 0."""
    w = w / w.sum(axis=-1, keepdims=True)
    nbar = (w * n).sum(axis=-1)
    lbar = (w * lat).sum(axis=-1)
    cov = (w * (n - nbar[..., None]) * (lat - lbar[..., None])).sum(axis=-1)
    var = (w * (n - nbar[..., None]) ** 2).sum(axis=-1)
    beta = cov / np.maximum(var, 1e-30)
    gamma = lbar - beta * nbar
    return np.maximum(beta, 1e-12), np.maximum(gamma, 0.0)


def fitted_models(cfg: dict, beta_t, gamma_t, n_task, r: np.random.Generator):
    """Simulate a short benchmark of every (platform, task) pair and fit
    its latency model, as the paper's Sec. III.A does."""
    pts = int(cfg["bench_points"])
    n_max = np.maximum(n_task[None, :] * cfg["bench_rep_fraction"],
                       6.0 * gamma_t / np.maximum(beta_t, 1e-30))
    n_max = np.minimum(np.maximum(n_max, 4 * 1024), n_task[None, :])
    grid = n_max[..., None] * (np.arange(1, pts + 1) / pts)
    truth = beta_t[..., None] * grid + gamma_t[..., None]
    noise = r.lognormal(0.0, cfg["bench_noise_sigma"], size=grid.shape)
    jitter = r.exponential(cfg["bench_jitter_share"] * gamma_t[..., None]
                           + 1e-3, size=grid.shape)
    meas = truth * noise + jitter
    return wls(grid, meas, 1.0 / np.maximum(meas, 1e-9))


def tenant_models(cfg: dict, seed: int) -> list:
    """``n_tenants`` fitted deployments: one task draw, one benchmark
    fit per tenant.  Each is a dict of plain arrays (beta, gamma (mu,
    tau); n (tau,); rho, pi (mu,)) and the platform names."""
    steps = task_steps(cfg, rng(seed, TASKS))
    beta_t, gamma_t = true_models(cfg, steps)
    plats = platforms(cfg)
    n = np.full(cfg["n_tasks"], float(cfg["n_paths"]))
    rho = np.array([p["quantum_s"] for p in plats])
    pi = np.array([p["rate_per_hour"] * p["quantum_s"] / SECONDS_PER_HOUR
                   for p in plats])
    names = tuple(p["name"] for p in plats)
    out = []
    for k in range(int(cfg["n_tenants"])):
        beta, gamma = fitted_models(cfg, beta_t, gamma_t, n,
                                    rng(seed, FIT, k))
        out.append(dict(beta=beta, gamma=gamma, n=n.copy(), rho=rho,
                        pi=pi, names=names))
    return out


def single_platform(m: dict):
    """(latency, billed cost) of each platform running the whole workload."""
    lat = (m["beta"] * m["n"][None, :] + m["gamma"]).sum(axis=1)
    return lat, np.ceil(lat / m["rho"]) * m["pi"]


def budget_range(m: dict, dead=None):
    """Budgets worth asking for on the live platforms: from the cheapest
    single platform to the billed cost of the latency-proportional split."""
    lat, cost = single_platform(m)
    alive = np.ones(lat.shape[0], bool) if dead is None else ~np.asarray(dead)
    w = np.where(alive, 1.0 / lat, 0.0)
    share = w / w.sum()
    g_l = (m["beta"] * m["n"][None, :] * share[:, None]
           + m["gamma"] * (share[:, None] > 1e-12)).sum(axis=1)
    c_split = float((np.ceil(g_l / m["rho"] - 1e-12) * m["pi"]).sum())
    c_low = float(cost[alive].min())
    return c_low, max(c_low, c_split)
