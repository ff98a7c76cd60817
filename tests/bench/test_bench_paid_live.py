"""The ``bnb.paid_live_pct`` reader: the share of the rows the lockstep
B&B's stacked solves paid that held a live node, on hand-made windows
and in a whole tiny traced run."""
from __future__ import annotations

import pytest

from bench import run

NAME = "bnb.paid_live_pct"
SEED = 2**31 + 23456


def _read(counters: dict):
    seen = run.Observed([], counters, {}, None, {"sweeps": [{}]})
    return run.load_metric(run.ROOT, NAME).read(seen)


@pytest.mark.parametrize("counters, want", [
    # 4 anchor rounds at width 1; rounds of 8, 16, 8 nodes at 8, 16, 8
    ({"milp.nodes": 36, "milp.dispatch_rows": 36, "milp.batch_rows": 52},
     100.0),
    # a round of 3 nodes dispatched at 4, one of 1 at width 1
    ({"milp.nodes": 4, "milp.dispatch_rows": 5, "milp.batch_rows": 8},
     80.0),
])
def test_reader_reads_a_hand_made_window(counters, want):
    assert _read(counters) == pytest.approx(want)


def test_reader_finds_nothing_in_an_empty_window():
    assert run.load_metric(run.ROOT, NAME).read(
        run.Observed([], {}, {}, None, {})) is None


def test_reader_finds_nothing_without_the_dispatch_counter():
    """A program that dispatches every round at the batch width counts
    no ``milp.dispatch_rows``: the reader returns None."""
    assert _read({"milp.rounds": 3, "milp.nodes": 32,
                  "milp.batch_rows": 48}) is None


def test_tiny_traced_run_reports_the_paid_live_share(tiny_root, monkeypatch):
    import jax
    monkeypatch.setattr(run, "enable_compile_cache", lambda root: None)
    monkeypatch.setattr(run, "chips_or_exit",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(run, "device_peaks", lambda root, kind: {})
    res = run.execute(tiny_root, "tiny.milp", SEED, 1.0, True,
                      log=lambda msg: None)
    assert res["compiles_in_window"] == 0
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert 0 < got[NAME] <= 100
    assert got[NAME] >= got["bnb.live_row_pct"]
