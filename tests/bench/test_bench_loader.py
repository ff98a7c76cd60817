"""The harness finds a cell's configuration, traffic mix and per-layer
metrics by name, including ones added as new files with no edit to any
file already there; and ``bench/run.py`` refuses to run without a TPU."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run

REPO = Path(__file__).resolve().parents[2]


def test_finds_every_cell_of_the_manifest():
    man = run.load_manifest(REPO)
    for w in man["workloads"]:
        cell = run.find_cell(REPO, w["name"])
        assert cell.config["name"] == w["config"]
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        for m in cell.per_layer:
            assert callable(run.load_metric(REPO, m["name"]).read)
        run.load_driver(cell.mix["driver"])


def test_new_files_are_found_without_edits(tiny_root, tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(tiny_root, root)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    cfg = json.loads((root / "bench/configs/tiny.json").read_text())
    cfg["name"] = "tiny-two"
    (root / "bench/configs/tiny-two.json").write_text(json.dumps(cfg))
    mix = json.loads((root / "bench/traffic/tiny_replans.json").read_text())
    mix["rate_per_s"] = 3.0
    (root / "bench/traffic/tiny_slow.json").write_text(json.dumps(mix))
    (root / "bench/metrics/client.requests.py").write_text(
        "def read(obs):\n    return float(len(obs.raw['latency_s']))\n")
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append(dict(man["configs"][0], name="tiny-two",
                               file="bench/configs/tiny-two.json"))
    man["workloads"].append(dict(man["workloads"][0], name="tiny.slow",
                                 config="tiny-two", traffic="tiny_slow"))
    man["per_layer"].append(dict(man["per_layer"][0],
                                 name="client.requests", unit="requests",
                                 workloads=["tiny.slow"]))
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    for p, b in before.items():
        assert p.read_bytes() == b

    cell = run.find_cell(root, "tiny.slow")
    assert cell.config["name"] == "tiny-two"
    assert cell.mix["rate_per_s"] == 3.0
    assert [m["name"] for m in cell.per_layer] == ["client.requests"]
    reader = run.load_metric(root, "client.requests")
    seen = run.Observed([], {}, {}, None, {"latency_s": [1.0, 2.0]})
    assert reader.read(seen) == 2.0


def test_unknown_workload_is_refused(tiny_root):
    with pytest.raises(SystemExit, match="no workload"):
        run.find_cell(tiny_root, "no.such.cell")


def test_unknown_device_kind_has_no_peaks():
    assert run.device_peaks(REPO, "TPU v5 lite")["bf16_flops_per_s"] > 0
    with pytest.raises(KeyError):
        run.device_peaks(REPO, "TPU v9 imaginary")


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper.milp",
         "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""
