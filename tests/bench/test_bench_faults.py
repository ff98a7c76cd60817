"""A whole benchmark run at tiny size on the CPU, clean and with the timed
path broken underneath: the clean run is correct, and each fault that a
cell can have turns ``correct`` false."""
from __future__ import annotations

import pytest

from bench import faults, run

SEED = 2**31 + 12345


def _run(root, cell: str) -> dict:
    return run.execute(root, cell, SEED, 1.0, False, log=lambda msg: None)


@pytest.fixture(autouse=True)
def cpu_run(monkeypatch):
    """Skip the harness's look for a chip (and its persistent compile
    cache): the rest of the run is the benchmark's own."""
    import jax
    monkeypatch.setattr(run, "enable_compile_cache", lambda root: None)
    monkeypatch.setattr(run, "chips_or_exit",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(run, "device_peaks", lambda root, kind: {})


@pytest.mark.parametrize("cell", ["tiny.replans", "tiny.milp",
                                  "tiny.regret"])
def test_clean_run_is_correct(tiny_root, cell):
    res = _run(tiny_root, cell)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert "setup_s" in res["metrics"] and len(res["metrics"]) >= 2
    assert list(res)[-1] == "checks"
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]


def test_traced_run_profiles_bnb_rounds(tiny_root, monkeypatch):
    from bench.drivers import milp
    slices = []
    orig = milp.trace_slice

    def trace_slice(st, profile):
        slices.append(profile)
        orig(st, profile)
    monkeypatch.setattr(milp, "trace_slice", trace_slice)
    res = run.execute(tiny_root, "tiny.milp", SEED, 1.0, True,
                      log=lambda msg: None)
    assert res["correct"] is True, res["checks"]
    prof, = slices
    assert prof.started and prof.stopped and len(prof.own_ns) == 2
    dev = res["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert "milp_sweep_s" not in res["metrics"]
    assert {"bnb.rounds_per_sweep", "device.idle_pct.milp"} <= set(
        res["metrics"])
    assert res["breakdown"]["device_ops"] and res["breakdown"]["idle_gaps"]


FAULT_CELLS = {"served": "tiny.replans", "milp": "tiny.milp",
               "regret": "tiny.regret"}


@pytest.mark.parametrize("cell,fault", sorted(
    (FAULT_CELLS[d], f) for d, f in faults.FAULTS))
def test_fault_makes_run_incorrect(tiny_root, monkeypatch, cell, fault):
    driver = {c: d for d, c in FAULT_CELLS.items()}[cell]
    faults.FAULTS[(driver, fault)](monkeypatch)
    res = _run(tiny_root, cell)
    assert res["correct"] is False, res["checks"]
