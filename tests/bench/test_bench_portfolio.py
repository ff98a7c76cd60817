"""The ``portfolio.milp`` cell's pieces: the ``milp_large`` driver's
set-up guard against dense node LPs, the three per-layer readers it
brings (on hand-made windows, on empty ones and on windows of a program
without what they read), the bytes floor they use, and whole tiny runs
through the driver."""
from __future__ import annotations

import json
import shutil
from typing import NamedTuple

import numpy as np
import pytest

from bench import data, devtrace, nodelp_bytes, run
from bench.drivers import milp_large
from repro.core.problem import AllocationProblem
from repro.obs import SpanEvent
from tinybench import tiny_config

SEED = 2**31 + 12345
NEW_METRICS = ("ipm.hbm_roofline_pct", "lp.put_kb_per_row", "sweep.seed_pct")
MS = 1_000_000


class DenseNodeLP(NamedTuple):
    """A node LP as a program that builds it densely would hold it."""
    c: np.ndarray
    a_eq: np.ndarray
    b_eq: np.ndarray
    g: np.ndarray
    h: np.ndarray
    lb: np.ndarray
    ub: np.ndarray


def _tiny_node():
    m = data.tenant_models(tiny_config(), SEED)[0]
    p = AllocationProblem(m["beta"], m["gamma"], m["n"], m["rho"], m["pi"])
    return p.node_lp(None)


def _dense(node) -> DenseNodeLP:
    return DenseNodeLP(*(np.asarray(getattr(node, f)) for f in
                         DenseNodeLP._fields))


def test_guard_counts_stored_arrays_only():
    node = _tiny_node()
    mu, tau = node.coef.shape
    n = mu * tau + mu + 1
    assert milp_large.held_bytes(node) == 8 * (mu * tau + 3 * mu + 2 * n)
    dense = _dense(node)
    assert milp_large.held_bytes(dense) == sum(v.nbytes for v in dense)
    assert milp_large.held_bytes(dense) > 2 * milp_large.held_bytes(node)


def test_guard_refuses_a_dense_node_and_passes_the_compact_one():
    node = _tiny_node()
    limit = 1.5 * milp_large.held_bytes(node)
    milp_large.guard(node, limit)
    with pytest.raises(SystemExit, match="dense node LP"):
        milp_large.guard(_dense(node), limit)


def test_guard_passes_the_cell_own_node():
    """The portfolio deployment's root node LP, as this program holds it,
    is far under the limit (the dense one would be 2.15 GB)."""
    cfg = data.load_config(run.ROOT, "portfolio4096x16")
    m = data.tenant_models(cfg, SEED)[0]
    p = AllocationProblem(m["beta"], m["gamma"], m["n"], m["rho"], m["pi"])
    assert p.tau == 4096 and p.mu == 16
    node = p.node_lp(None)
    assert milp_large.held_bytes(node) < 0.01 * milp_large.LIMIT_BYTES
    milp_large.guard(node)


def test_bytes_floor_of_a_row_iteration():
    mu, tau = 16, 4096
    cols = mu * tau + mu + 1 + 2 * mu
    want = 8 * (2 * mu * tau + 2 * mu * mu + 2 * mu * (3 * mu + 1)
                + 2 * (4 * cols + tau + 2 * mu))
    assert nodelp_bytes.per_row_iteration(mu, tau) == want
    # the operator and iterate grow with mu * tau
    assert (nodelp_bytes.per_row_iteration(16, 4096)
            > 25 * nodelp_bytes.per_row_iteration(16, 128))


# ---------------------------------------------------------------------------
# The readers on hand-made windows
# ---------------------------------------------------------------------------

def _span(name, ts_ms, dur_ms, depth=0, **attrs):
    return SpanEvent(name, ts_ms * MS, dur_ms * MS, 1, depth, attrs or None)


def _window(with_new=True):
    """A traced sweep: the profiler ran from 400 to 900 ms (host clock),
    holding two node solves; the trace clock is offset by 10 s."""
    node_attrs = dict(mu=16, tau=128) if with_new else {}
    spans = [
        _span("pareto.sweep", 0, 1000, n_points=8),
        _span("milp.seed", 10, 30, 1),
        _span("milp.seed", 300, 20, 2),
        _span("lp.solve_stacked", 100, 200, 2, width=8, paid_rows=80,
              **node_attrs),
        _span("lp.solve_stacked", 450, 100, 2, width=16, paid_rows=160,
              **node_attrs),
        _span("lp.solve_stacked", 600, 200, 2, width=8, paid_rows=80,
              **node_attrs),
    ]
    if not with_new:
        spans = [s for s in spans if s.name != "milp.seed"]
    off = 10_000 * MS
    ops = [devtrace.Op("fusion.1", "jit_node_ipm", "/device:TPU:0",
                       off + 460 * MS, off + 540 * MS, 80 * MS),
           devtrace.Op("copy.2", "jit_node_ipm", "/device:TPU:0",
                       off + 610 * MS, off + 790 * MS, 150 * MS),
           devtrace.Op("fusion.3", "jit_other", "/device:TPU:0",
                       off + 620 * MS, off + 640 * MS, 20 * MS)]
    host = {"lp.solve_stacked": [(off + 450 * MS, off + 550 * MS, 2),
                                 (off + 600 * MS, off + 800 * MS, 2)]}
    trace = devtrace.Summary((off + 400 * MS, off + 900 * MS), ops, {},
                             host)
    counters = ({"lp.put_bytes": 3 * 1024 * 50, "lp.put_rows": 3}
                if with_new else {"milp.rounds": 2})
    return run.Observed(spans, counters, {}, trace, {"sweeps": [{}]},
                        [(390 * MS, 400 * MS), (900 * MS, 950 * MS)])


def _reader(name, monkeypatch):
    mod = run.load_metric(run.ROOT, name)
    if name == "ipm.hbm_roofline_pct":
        monkeypatch.setattr(mod, "_hbm_bytes_per_s", lambda: 819e9)
    return mod


EXPECTED = {
    "ipm.hbm_roofline_pct": 100.0 * 240 * nodelp_bytes.per_row_iteration(
        16, 128) / ((80 + 150) * 1e-3 * 819e9),
    "lp.put_kb_per_row": 50.0,
    # the profiler's own start and stop (10 + 50 ms) are left out
    "sweep.seed_pct": 100.0 * (30 + 20) / (1000 - 60),
}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_reads_a_hand_made_window(name, monkeypatch):
    assert _reader(name, monkeypatch).read(_window()) == pytest.approx(
        EXPECTED[name])


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_finds_nothing_in_an_empty_window(name, monkeypatch):
    empty = run.Observed([], {}, {}, None, {})
    assert _reader(name, monkeypatch).read(empty) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_finds_nothing_without_what_this_program_adds(name,
                                                            monkeypatch):
    """The parent program: no ``mu``/``tau`` on its solves, no put
    counters, no ``milp.seed`` spans; each reader returns None."""
    assert _reader(name, monkeypatch).read(_window(False)) is None


def test_roofline_leaves_out_solves_the_device_record_lost(monkeypatch):
    """A solve that ends after the last device op the profiler kept is
    left out of the bytes and of the time alike."""
    seen = _window()
    seen.trace.window = (seen.trace.window[0],
                         seen.trace.window[0] + 300 * MS)
    got = _reader("ipm.hbm_roofline_pct", monkeypatch).read(seen)
    assert got == pytest.approx(100.0 * 160 * nodelp_bytes.per_row_iteration(
        16, 128) / (80e-3 * 819e9))


# ---------------------------------------------------------------------------
# Whole tiny runs through the driver
# ---------------------------------------------------------------------------

@pytest.fixture
def large_root(tiny_root, tmp_path):
    """The tiny checkout with a ``tiny.milp_large`` cell: the tiny MILP
    mix driven by ``milp_large``, reporting the new metrics."""
    root = tmp_path / "checkout"
    shutil.copytree(tiny_root, root)
    shutil.copy(run.ROOT / "bench/peaks.json", root / "bench/peaks.json")
    mix = json.loads((root / "bench/traffic/tiny_milp.json").read_text())
    mix["driver"] = "milp_large"
    (root / "bench/traffic/tiny_milp_large.json").write_text(json.dumps(mix))
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["workloads"].append(dict(name="tiny.milp_large", config="tiny",
                                 traffic="tiny_milp_large", chips=1,
                                 why="CPU tests"))
    for m in man["end_to_end"]:
        if m["name"] == "milp_sweep_s":
            m["workloads"].append("tiny.milp_large")
    for name in NEW_METRICS + ("bnb.rounds_per_sweep",):
        man["per_layer"].append(dict(
            name=name, unit="1", better="lower", source="host_clock",
            layer="tiny", moves="milp_sweep_s",
            workloads=["tiny.milp_large"]))
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root


@pytest.fixture
def cpu_run(monkeypatch):
    import jax
    monkeypatch.setattr(run, "enable_compile_cache", lambda root: None)
    monkeypatch.setattr(run, "chips_or_exit",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(run, "device_peaks", lambda root, kind: {})


def test_tiny_traced_run_through_the_large_driver(large_root, cpu_run):
    res = run.execute(large_root, "tiny.milp_large", SEED, 1.0, True,
                      log=lambda msg: None)
    assert res["correct"] is True, res["checks"]
    assert res["compiles_in_window"] == 0
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert got["lp.put_kb_per_row"] > 0
    assert 0 < got["sweep.seed_pct"] < 100
    assert got["bnb.rounds_per_sweep"] > 0
    # no published HBM peak for a CPU: the roofline share is left out
    assert "ipm.hbm_roofline_pct" not in got
    modules = {name.split("/")[0] for name, _ in
               res["breakdown"]["device_ops"]}
    assert any(m.startswith("jit_node_ipm") for m in modules), modules


def test_tiny_run_refuses_a_dense_node_lp(large_root, cpu_run,
                                          monkeypatch):
    """A program whose node LPs hold their dense rows: the run stops in
    set-up, before any solve, naming the dense node LP."""
    from repro.core import lp
    node = _tiny_node()
    dense = _dense(node)
    monkeypatch.setattr(AllocationProblem, "node_lp",
                        lambda self, *a, **k: dense)
    monkeypatch.setattr(milp_large, "LIMIT_BYTES",
                        1.5 * milp_large.held_bytes(node))
    monkeypatch.setattr(lp, "solve_lp_stacked", None)
    with pytest.raises(SystemExit, match="dense node LP"):
        run.execute(large_root, "tiny.milp_large", SEED, 1.0, False,
                    log=lambda msg: None)


# ---------------------------------------------------------------------------
# the control at the cell's size: the plain interior point through the
# border of its rows (``bench/reference/lp_border.py``)

def _border_cases():
    from bench.reference import lp as ref_lp
    m = data.tenant_models(tiny_config(), SEED)[0]
    lo, hi = data.budget_range(m)
    mu, tau = m["beta"].shape
    fixed0 = np.zeros((mu, tau), bool)
    fixed0[0, :2] = True
    fixed1 = np.zeros((mu, tau), bool)
    fixed1[1, 2:4] = True
    return [ref_lp.build(m, cap) for cap in (None, lo, 0.5 * (lo + hi), hi)
            ] + [ref_lp.build(m, hi, fixed0=fixed0, fixed1=fixed1,
                              d_lb=np.ones(mu))]


@pytest.mark.parametrize("case", range(5))
def test_border_reference_is_the_dense_one_in_float64(case):
    from bench.reference import lp as ref_lp
    from bench.reference import lp_border
    lp = _border_cases()[case]
    ref, _ = ref_lp.solve_highs(lp)
    dense, _ = ref_lp.solve_ipm(lp, np.float64)
    obj, x = lp_border.solve_ipm(lp, np.float64)
    assert abs(obj - ref) / ref < 1e-8
    assert abs(obj - dense) / ref < 1e-8
    assert ref_lp.residual(lp, x) < 1e-9


def test_border_reference_refuses_rows_without_a_diagonal_block():
    import scipy.sparse as sp
    from bench.reference import lp_border
    e = sp.csr_matrix(np.array([[1.0, 1.0, 0.0], [0.0, 1.0, 1.0]]))
    with pytest.raises(ValueError, match="two task rows"):
        lp_border._border_solver(e, sp.csr_matrix(np.ones((1, 3))),
                                 np.float64)


def test_border_control_fails_the_large_cell_limits():
    """A window of the large mix on the tiny deployment: the program
    passes every limit of ``milp_sweep_large``, and the float32 control
    of ``bench/control_border.py`` fails at least one of them."""
    from bench import control_border
    from bench.drivers import milp as driver
    mix = json.loads((run.ROOT / "bench/traffic/milp_sweep_large.json")
                     .read_text())
    st = driver.setup(tiny_config(), mix, SEED, 1.0, log=lambda msg: None)
    raw = driver.window(st, 1.0)
    driver.release(st)
    checks = driver.check(st, raw, SEED, log=lambda msg: None)
    assert all(c["value"] <= c["limit"] for c in checks.values()), checks
    ctrl = control_border.milp_border_control(st, raw, SEED, 8)
    lim = dict(mix["limits"], bound_excess=mix["gap_tol"])
    assert ctrl["relax_gap_compared"] > 0 and ctrl["node_gap_compared"] > 0
    assert ctrl["alloc_gap"] > lim["alloc_gap"], ctrl
