"""A tiny checkout of the benchmark for CPU tests: the real drivers,
references and metric readers, driven through cells whose configuration
and traffic files are small enough for the CPU.  (A module of its own,
not the conftest, so that test files import it by a name no other
directory uses.)"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (REPO, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def tiny_config() -> dict:
    cfg = json.loads((REPO / "bench/configs/paper128x16.json").read_text())
    cfg.update(name="tiny", n_tasks=6, n_paths=2_000_000, n_tenants=2,
               platforms=[dict(cfg["platforms"][k], count=c)
                          for k, c in ((0, 2), (3, 1), (4, 1))])
    return cfg


def tiny_spot_config() -> dict:
    cfg = json.loads(
        (REPO / "bench/configs/spot16-megadiverse.json").read_text())
    cfg.update(name="tiny-spot", problem_from="tiny", n_initial=2,
               max_platforms=4)
    return cfg


def tiny_mixes() -> dict:
    def mix(name, **kw):
        m = json.loads((REPO / f"bench/traffic/{name}.json").read_text())
        m.update(kw)
        return m
    return {
        "tiny_replans": mix("replans_poisson", rate_per_s=200.0,
                            ladder_max=8),
        "tiny_milp": mix("milp_sweep", n_points=3, node_limit=60,
                         highs_points=1000, highs_time_limit_s=5.0),
        "tiny_regret": mix("regret_resplit", n_episodes=16,
                           check_episodes=4),
    }


def write_tiny_root(root: Path) -> Path:
    """Lay out a checkout holding only tiny cells; the drivers and
    references are imported from the repo itself."""
    (root / "bench/configs").mkdir(parents=True, exist_ok=True)
    (root / "bench/traffic").mkdir(parents=True, exist_ok=True)
    shutil.copytree(REPO / "bench/metrics", root / "bench/metrics",
                    dirs_exist_ok=True)
    for cfg in (tiny_config(), tiny_spot_config()):
        (root / f"bench/configs/{cfg['name']}.json").write_text(
            json.dumps(cfg))
    for name, mix in tiny_mixes().items():
        (root / f"bench/traffic/{name}.json").write_text(json.dumps(mix))
    # every driver gets a tiny cell, whether or not its chip cell is in
    # the manifest (a cell stays out while a program fault stands)
    man = json.loads((REPO / "BENCHMARK.json").read_text())
    cells = {"tiny.replans": ("tiny", "tiny_replans",
                              ["frontier_p50_s", "frontier_p95_s"], "serve"),
             "tiny.milp": ("tiny", "tiny_milp", ["milp_sweep_s"], "milp"),
             "tiny.regret": ("tiny-spot", "tiny_regret", ["episodes_per_s"],
                             "regret")}
    man["configs"] = [dict(name=n, source="tiny test configuration",
                           file=f"bench/configs/{n}.json", reduced=[],
                           why="CPU tests") for n in ("tiny", "tiny-spot")]
    man["workloads"] = [dict(name=c, config=cfg, traffic=t, chips=1,
                             why="CPU tests")
                        for c, (cfg, t, _, _) in cells.items()]
    man["end_to_end"] = [dict(name="setup_s", unit="s", better="lower",
                              bound=0.25, source="host_clock")]
    man["per_layer"] = []
    metrics = {p.stem for p in (REPO / "bench/metrics").glob("*.py")}
    for c, (_, _, e2e, tag) in cells.items():
        for name in e2e:
            man["end_to_end"].append(dict(
                name=name, unit="1", better="lower", bound=0.25,
                source="host_clock", workloads=[c]))
        for name in sorted(metrics):
            owner = {"client": "serve", "server": "serve", "host": "serve",
                     "bnb": "milp", "fused": "regret"}.get(
                         name.split(".")[0], name.rsplit(".", 1)[-1])
            if owner == tag:
                man["per_layer"].append(dict(
                    name=name, unit="1", better="lower",
                    source="host_clock", layer="tiny", moves=e2e[0],
                    workloads=[c]))
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root
