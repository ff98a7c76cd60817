"""The program's spans and counters on the lockstep B&B path, and the
per-layer readers built on them: how the spans nest in a traced sweep on
the tiny problem, what the counters and span attributes count, each
reader on a hand-made window, and a whole tiny traced run reporting
every reader's metric."""
from __future__ import annotations

import numpy as np
import pytest

from bench import data, run
from repro import obs
from repro.core import lp, pareto
from repro.core.problem import AllocationProblem
from repro.obs import SpanEvent
from tinybench import tiny_config, tiny_mixes

SEED = 2**31 + 12345
NEW_METRICS = ("ipm.ms_per_row_iter.milp", "bnb.live_row_pct",
               "bnb.assemble_ms_per_round", "sweep.bnb_pct.milp")


def _tiny_problem() -> AllocationProblem:
    m = data.tenant_models(tiny_config(), SEED)[0]
    return AllocationProblem(m["beta"], m["gamma"], m["n"], m["rho"],
                             m["pi"], m["names"])


def _inside(child, parent) -> bool:
    return (child.tid == parent.tid and parent.ts_ns <= child.ts_ns
            and child.ts_ns + child.dur_ns <= parent.ts_ns + parent.dur_ns)


def _children(events, parent, name) -> list:
    return [e for e in events if e.name == name and _inside(e, parent)
            and e.depth == parent.depth + 1]


@pytest.fixture(scope="module")
def traced_sweep():
    """One traced tiny sweep: its spans, its counters, and the Newton
    ledger's delta across each stacked call, in call order."""
    mix = tiny_mixes()["tiny_milp"]
    kw = dict(n_points=int(mix["n_points"]), node_limit=8,
              time_limit_s=float("inf"), gap_tol=float(mix["gap_tol"]),
              newton_dtype=mix["newton_dtype"])
    problem = _tiny_problem()
    pareto.milp_tradeoff_batched(problem, **kw)          # compile
    deltas = []
    solve = lp.solve_lp_stacked

    def counted(*args, **kwargs):
        keys = ("lockstep_rows", "active_rows", "compact_rows")
        before = [obs.read_counter(f"lp.newton.{k}") for k in keys]
        out = solve(*args, **kwargs)
        deltas.append({k: obs.read_counter(f"lp.newton.{k}") - b
                       for k, b in zip(keys, before)})
        return out

    lp.solve_lp_stacked = counted
    obs.enable()
    try:
        with obs.scope() as scoped:
            pareto.milp_tradeoff_batched(problem, **kw)
    finally:
        obs.disable()
        lp.solve_lp_stacked = solve
    events = sorted(obs.trace_events(), key=lambda e: e.ts_ns)
    obs.clear_trace()
    return events, scoped["counters"], deltas


def test_round_children_nest_inside_each_round(traced_sweep):
    events, _, _ = traced_sweep
    rounds = [e for e in events if e.name == "milp.round"]
    assert rounds
    for r in rounds:
        for name in ("milp.assemble", "lp.solve_stacked", "milp.fetch",
                     "milp.expand"):
            assert len(_children(events, r, name)) == 1, (name, r)
        # the np.stack of the node LPs and their copy to the device
        assert len(_children(events, r, "lp.put")) == 2
        solve, = _children(events, r, "lp.solve_stacked")
        for name in ("lp.dispatch", "lp.device_wait", "lp.ledger"):
            assert len(_children(events, solve, name)) == 1, name
        order = [e.name for e in events if e.depth == r.depth + 1
                 and _inside(e, r)]
        assert order == ["milp.assemble", "lp.put", "lp.put",
                         "lp.solve_stacked", "milp.fetch", "milp.expand"]
    for h in (e for e in events if e.name == "milp.host_resolve"):
        assert any(_inside(h, x) for x in events if x.name == "milp.expand")


def test_sweep_phases_cover_the_sweep(traced_sweep):
    events, _, _ = traced_sweep
    sweep, = [e for e in events if e.name == "pareto.sweep"]
    assert sweep.attrs == {"n_points": tiny_mixes()["tiny_milp"]["n_points"]}
    phases = [e for e in events if e.name in ("pareto.anchor",
                                              "pareto.relaxation",
                                              "pareto.bnb")]
    assert [e.name for e in phases] == ["pareto.anchor", "pareto.relaxation",
                                        "pareto.bnb"]
    assert all(_inside(e, sweep) and e.depth == sweep.depth + 1
               for e in phases)
    assert sum(e.dur_ns for e in phases) >= 0.95 * sweep.dur_ns
    anchor, relax, bnb = phases
    rounds = [e for e in events if e.name == "milp.round"]
    assert {r.attrs["width"] for r in rounds if _inside(r, anchor)} == {1}
    assert not any(_inside(r, relax) for r in rounds)
    assert any(_inside(r, bnb) and r.attrs["width"] > 1 for r in rounds)


def test_batch_rows_count_the_width_of_every_round(traced_sweep):
    events, counters, _ = traced_sweep
    rounds = [e for e in events if e.name == "milp.round"]
    anchor, bnb = ([r for r in rounds if any(_inside(r, p) for p in events
                                              if p.name == name)]
                   for name in ("pareto.anchor", "pareto.bnb"))
    width, = {r.attrs["width"] for r in bnb}
    assert len(anchor) + len(bnb) == len(rounds) == counters["milp.rounds"]
    assert counters["milp.batch_rows"] == len(anchor) + width * len(bnb)
    assert counters["milp.nodes"] == sum(r.attrs["popped"] for r in rounds)
    assert counters["milp.nodes"] < counters["milp.batch_rows"]


def test_paid_rows_are_each_calls_ledger_delta(traced_sweep):
    events, _, deltas = traced_sweep
    solves = [e for e in events if e.name == "lp.solve_stacked"]
    assert len(solves) == len(deltas) > 2
    for s, d in zip(solves, deltas):
        assert s.attrs["paid_rows"] == d["lockstep_rows"] > 0
        assert s.attrs["active_rows"] == d["active_rows"]
        assert s.attrs["iters_max"] * s.attrs["width"] == d["lockstep_rows"]


@pytest.mark.parametrize("compact", [False, True])
def test_paid_rows_is_what_the_driver_paid(compact):
    problem = _tiny_problem()
    caps = np.linspace(1.0, 3.0, 4) * problem.single_platform_cost().min()
    nodes = pareto.frontier_nodes(problem, caps)
    active = np.array([True, False, True, False])
    lp.solve_node_lps_stacked(nodes, row_active=active, compact=compact)
    obs.enable()
    try:
        with obs.scope() as scoped:
            lp.solve_node_lps_stacked(nodes, row_active=active,
                                      compact=compact)
    finally:
        obs.disable()
    events = obs.trace_events()
    obs.clear_trace()
    solve, = [e for e in events if e.name == "lp.solve_stacked"]
    led = scoped["counters"]
    key = "lp.newton.compact_rows" if compact else "lp.newton.lockstep_rows"
    assert solve.attrs["paid_rows"] == led[key] > 0
    assert solve.attrs["active_rows"] == led["lp.newton.active_rows"]
    dispatch, = _children(events, solve, "lp.dispatch")
    chunks = [e for e in events if e.name == "lp.chunk"]
    assert bool(chunks) == compact
    assert all(_inside(c, dispatch) for c in chunks)


# ---------------------------------------------------------------------------
# The readers on hand-made windows
# ---------------------------------------------------------------------------

MS = 1_000_000


def _span(name, ts_ms, dur_ms, depth=0, tid=1, **attrs):
    return SpanEvent(name, ts_ms * MS, dur_ms * MS, tid, depth, attrs or None)


def _window():
    spans = [
        _span("pareto.sweep", 0, 1000, n_points=8),
        _span("pareto.anchor", 0, 100, 1),
        _span("pareto.relaxation", 100, 200, 1),
        _span("pareto.bnb", 300, 700, 1),
        _span("milp.round", 300, 300, 2, round=1, popped=3, width=4),
        _span("milp.assemble", 300, 5, 3),
        _span("lp.put", 305, 2, 3),
        _span("lp.put", 307, 1, 3),
        _span("lp.solve_stacked", 308, 200, 3, width=4, active_rows=30,
              paid_rows=40, iters_max=10),
        _span("milp.round", 700, 300, 2, round=2, popped=1, width=4),
        _span("milp.assemble", 700, 3, 3),
        _span("lp.put", 703, 3, 3),
        _span("lp.solve_stacked", 706, 100, 3, width=4, active_rows=8,
              paid_rows=40, iters_max=10),
        # a stacked solve outside any round: its put is no round's work
        _span("lp.put", 150, 7, 2),
        _span("lp.solve_stacked", 157, 100, 2, width=8, active_rows=100,
              paid_rows=120, iters_max=15),
    ]
    counters = {"milp.rounds": 2, "milp.nodes": 4, "milp.batch_rows": 8}
    # the run stopped the profiler late in the second round
    return run.Observed(spans, counters, {}, None, {"sweeps": [{}]},
                        [(900 * MS, 950 * MS)])


EXPECTED = {
    "ipm.ms_per_row_iter.milp": 400.0 / 200,        # ms over paid rows
    "bnb.live_row_pct": 50.0,
    "bnb.assemble_ms_per_round": (5 + 3 + 2 + 1 + 3) / 2,
    "sweep.bnb_pct.milp": 100.0 * (700 - 50) / (1000 - 50),
}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_reads_a_hand_made_window(name):
    reader = run.load_metric(run.ROOT, name)
    assert reader.read(_window()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_finds_nothing_in_an_empty_window(name):
    reader = run.load_metric(run.ROOT, name)
    assert reader.read(run.Observed([], {}, {}, None, {})) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_finds_nothing_without_the_new_spans(name):
    """A program without these spans, counters and attributes (the one
    the benchmark had before them): each reader returns None."""
    spans = [_span("milp.round", 0, 300, round=1, popped=3, width=4),
             _span("lp.solve_stacked", 10, 200, 1, width=4, compact=False)]
    seen = run.Observed(spans, {"milp.rounds": 1, "milp.nodes": 3}, {},
                        None, {"sweeps": [{}]})
    assert run.load_metric(run.ROOT, name).read(seen) is None


def test_tiny_traced_run_reports_every_new_metric(tiny_root, monkeypatch):
    import jax
    monkeypatch.setattr(run, "enable_compile_cache", lambda root: None)
    monkeypatch.setattr(run, "chips_or_exit",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(run, "device_peaks", lambda root, kind: {})
    res = run.execute(tiny_root, "tiny.milp", SEED, 1.0, True,
                      log=lambda msg: None)
    assert res["correct"] is True, res["checks"]
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(NEW_METRICS) <= set(got)
    assert got["ipm.ms_per_row_iter.milp"] > 0
    assert 0 < got["bnb.live_row_pct"] <= 100
    assert got["bnb.assemble_ms_per_round"] > 0
    assert 0 < got["sweep.bnb_pct.milp"] < 100
    for name in ("bnb.rounds_per_sweep", "bnb.host_ms_per_round",
                 "ipm.iters_per_row.milp", "device.idle_pct.milp"):
        assert name in got
