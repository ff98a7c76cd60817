"""Unified telemetry layer: span tracing (nesting, exporters, disabled
no-op contract), the thread-safe metrics registry with scope frames,
compile-event attribution, and the single-registry snapshot."""
import json
import threading
import tracemalloc

import numpy as np
import pytest

from repro import obs
from repro.core import lp, pareto
from repro.core.problem import AllocationProblem
from repro.serving import AllocRequest, AllocationServer


@pytest.fixture(autouse=True)
def _tracing_off():
    """Every test starts and ends with tracing disabled and no leftover
    spans, whatever happened before it."""
    obs.disable()
    obs.clear_trace()
    yield
    obs.disable()
    obs.clear_trace()


def _problem(seed=0, mu=4, tau=6):
    rng = np.random.default_rng(seed)
    return AllocationProblem(rng.uniform(0.5, 2.0, (mu, tau)) * 1e-3,
                             rng.uniform(0.1, 1.0, (mu, tau)),
                             rng.uniform(50.0, 200.0, tau),
                             rng.uniform(60.0, 600.0, mu),
                             rng.uniform(0.1, 2.0, mu))


def _caps(problem, k, lo=1.0, hi=3.0):
    c_l = float(problem.single_platform_cost().min())
    return np.linspace(lo * c_l, hi * c_l, k)


# ---------------------------------------------------------------------------
# Span tracing
# ---------------------------------------------------------------------------

def test_span_nesting_depth_and_attrs():
    obs.enable()
    with obs.span("outer", kind="a"):
        with obs.span("inner") as sp:
            sp.set(extra=7)
    obs.disable()
    events = {e.name: e for e in obs.trace_events()}
    assert set(events) == {"outer", "inner"}
    assert events["outer"].depth == 0 and events["inner"].depth == 1
    assert events["outer"].attrs == {"kind": "a"}
    assert events["inner"].attrs == {"extra": 7}
    # the parent interval encloses the child
    o, i = events["outer"], events["inner"]
    assert o.ts_ns <= i.ts_ns
    assert i.ts_ns + i.dur_ns <= o.ts_ns + o.dur_ns


def test_capture_scopes_enablement():
    assert not obs.enabled()
    with obs.capture():
        assert obs.enabled()
        with obs.span("inside"):
            pass
    assert not obs.enabled()
    assert [e.name for e in obs.trace_events()] == ["inside"]


def test_disabled_span_is_strict_noop():
    """Disabled-mode spans add no events, share one singleton and
    retain no memory."""
    assert obs.span("a") is obs.span("b")          # stateless singleton
    with obs.span("never", x=1) as sp:
        sp.set(y=2)
    assert obs.trace_events() == []
    # no *retained* allocations across a large disabled-span loop
    tracemalloc.start()
    before = tracemalloc.get_traced_memory()[0]
    for _ in range(10_000):
        with obs.span("noop"):
            pass
    retained = tracemalloc.get_traced_memory()[0] - before
    tracemalloc.stop()
    assert retained < 4096, f"disabled spans retained {retained} bytes"


def test_profiler_mirror_resolves_the_annotation_once(monkeypatch):
    """``enable(jax_profiler=True)`` looks ``TraceAnnotation`` up once:
    every span then opens and closes one, and ``disable`` ends that."""
    import jax.profiler
    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    obs.enable(jax_profiler=True)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", None)
    with obs.span("outer"):
        with obs.span("inner"):
            pass
    obs.disable()
    assert seen == [("enter", "outer"), ("enter", "inner"),
                    ("exit", "inner"), ("exit", "outer")]
    obs.enable()
    with obs.span("plain"):
        pass
    assert len(seen) == 4
    assert [e.name for e in obs.trace_events()] == ["plain"]


def test_add_span_records_external_window():
    obs.enable()
    obs.add_span("lifecycle", 1_000, 5_000, tenant="t0")
    obs.disable()
    (ev,) = obs.trace_events()
    assert (ev.name, ev.ts_ns, ev.dur_ns) == ("lifecycle", 1_000, 4_000)
    assert ev.attrs == {"tenant": "t0"}


def test_chrome_trace_export_golden(tmp_path):
    """Chrome trace-event JSON: one complete "X" event per span, sorted
    timestamps, microsecond units, attrs in args."""
    obs.enable()
    with obs.span("s.outer", width=4):
        with obs.span("s.inner"):
            pass
    with obs.span("s.second"):
        pass
    obs.disable()
    path = tmp_path / "trace.json"
    n = obs.export_chrome_trace(str(path))
    assert n == 3
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    assert len(evs) == 3
    assert all(e["ph"] == "X" for e in evs)
    assert all(e["dur"] >= 0 for e in evs)
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)
    assert [e["name"] for e in evs] == ["s.outer", "s.inner", "s.second"]
    outer = next(e for e in evs if e["name"] == "s.outer")
    assert outer["args"] == {"width": 4}


def test_jsonl_export(tmp_path):
    obs.enable()
    with obs.span("one", k="v"):
        pass
    obs.disable()
    path = tmp_path / "trace.jsonl"
    assert obs.export_jsonl(str(path)) == 1
    (line,) = path.read_text().strip().splitlines()
    rec = json.loads(line)
    assert rec["name"] == "one" and rec["args"] == {"k": "v"}
    assert rec["dur_us"] >= 0


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_registry_counters_gauges_hists():
    reg = obs.MetricsRegistry()
    reg.inc("c", 2)
    reg.inc("c")
    reg.gauge("g", 1.5)
    reg.gauge("g", 2.5)
    reg.observe_many("h", [1.0, 3.0, 2.0])
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 3
    assert snap["gauges"]["g"] == 2.5
    h = snap["histograms"]["h"]
    assert (h["count"], h["min"], h["max"], h["p50"]) == (3, 1.0, 3.0, 2.0)


def test_registry_scope_reads_zero_based_and_merges_up():
    reg = obs.MetricsRegistry()
    reg.inc("n", 5)
    with reg.scope() as scoped:
        assert reg.read_counter("n") == 0          # fresh frame
        reg.inc("n", 2)
        reg.observe("h", 1.0)
        with reg.scope() as inner:
            reg.inc("n", 1)
        assert inner["counters"]["n"] == 1
        assert reg.read_counter("n") == 3          # inner merged up
    assert scoped["counters"]["n"] == 3
    assert scoped["histograms"]["h"] == [1.0]
    assert reg.read_counter("n") == 8              # outer sees everything
    assert reg.snapshot()["counters"]["n"] == 8


def test_registry_threaded_no_lost_updates():
    """The module-level ledger predecessor lost concurrent updates; the
    registry must not."""
    reg = obs.MetricsRegistry()
    n_threads, n_iter = 8, 2000

    def worker():
        for _ in range(n_iter):
            reg.update(counters={"hits": 1}, observations={"lat": [1.0]})

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = reg.snapshot()
    assert snap["counters"]["hits"] == n_threads * n_iter
    assert snap["histograms"]["lat"]["count"] == n_threads * n_iter


# ---------------------------------------------------------------------------
# Compile-event attribution
# ---------------------------------------------------------------------------

def test_compile_events_filtering():
    mark = obs.last_seq()
    obs.record_compile("stacked", width=8, linsolve="xla", row_shape=(1,))
    obs.record_compile("stacked", width=4, linsolve="ref", row_shape=(1,))
    obs.record_compile("compact", width=8, linsolve="xla", row_shape=(2,))
    assert obs.compile_count(since_seq=mark) == 3
    assert obs.compile_count(kind="stacked", since_seq=mark) == 2
    assert obs.compile_count(since_seq=mark, linsolve="xla") == 2
    assert obs.compile_count(since_seq=mark, width=8, linsolve="xla") == 2
    assert obs.compile_count(kind="compact", since_seq=mark, width=8) == 1
    # keys absent from an event's config never match
    assert obs.compile_count(since_seq=mark, nonexistent=1) == 0
    evs = obs.compile_events(since_seq=mark, linsolve="ref")
    assert len(evs) == 1 and evs[0].config["width"] == 4
    # the watermark cuts earlier events off
    assert obs.compile_count(since_seq=obs.last_seq()) == 0


def test_compile_events_disambiguate_mesh_from_unsharded():
    """The attribution gap: every stacked signature records its mesh in
    the config (``mesh_shape``), so a query built for one mesh can never
    silently match solves run under a different mesh — or no mesh.  A
    1-device mesh still takes the sharded code path, so this regression
    test runs in the tier-1 (single-CPU) suite."""
    from repro.launch.mesh import make_solver_mesh
    p = _problem(41, mu=3, tau=8)                  # fresh shape
    nodes = pareto.frontier_nodes(p, _caps(p, 3))
    mesh = make_solver_mesh()
    mark = obs.last_seq()
    lp.solve_node_lps_stacked(nodes)
    lp.solve_node_lps_stacked(nodes, mesh=mesh)
    evs = obs.compile_events(kind="stacked", since_seq=mark)
    assert len(evs) == 2                           # distinct jit keys
    shapes = {ev.config["mesh_shape"] for ev in evs}
    n_dev = lp.mesh_n_shards(mesh)
    assert shapes == {None, (("lp_rows", n_dev),)}
    # filters select exactly one side each — never both
    assert obs.compile_count(kind="stacked", since_seq=mark,
                             mesh_shape=None) == 1
    assert obs.compile_count(kind="stacked", since_seq=mark,
                             mesh_shape=(("lp_rows", n_dev),)) == 1
    # a mesh that never ran matches nothing
    assert obs.compile_count(kind="stacked", since_seq=mark,
                             mesh_shape=(("lp_rows", n_dev + 1),)) == 0
    # attribution keys carry the same field on both sides
    assert lp.stacked_attribution_key(nodes[0])["mesh_shape"] is None
    assert lp.stacked_attribution_key(
        nodes[0], mesh=mesh)["mesh_shape"] == (("lp_rows", n_dev),)
    # warm caches on both sides: re-solving records nothing
    mark2 = obs.last_seq()
    lp.solve_node_lps_stacked(nodes)
    lp.solve_node_lps_stacked(nodes, mesh=mesh)
    assert obs.compile_count(since_seq=mark2) == 0


def test_stacked_solve_records_attributable_compile_events():
    """A fresh stacked shape records exactly one compile event carrying
    the solve config; re-solving the same shape records none."""
    p = _problem(40, mu=3, tau=7)                  # fresh shape
    nodes = pareto.frontier_nodes(p, _caps(p, 3))
    mark = obs.last_seq()
    lp.solve_node_lps_stacked(nodes)
    evs = obs.compile_events(kind="stacked", since_seq=mark)
    assert len(evs) == 1
    cfg = evs[0].config
    assert cfg["width"] == 3 and cfg["linsolve"] == "xla"
    assert cfg["compact"] is False and cfg["newton_dtype"] == "float64"
    key = lp.stacked_attribution_key(nodes[0])
    assert cfg["row_shape"] == key["row_shape"]
    assert cfg["axes"] == key["axes"]
    mark2 = obs.last_seq()
    lp.solve_node_lps_stacked(nodes)               # cache hit
    assert obs.compile_count(since_seq=mark2) == 0


# ---------------------------------------------------------------------------
# One-registry snapshot + instrumented serving episode
# ---------------------------------------------------------------------------

def test_snapshot_unifies_solver_serving_and_market_metrics():
    p = _problem(0)
    srv = AllocationServer(ladder_max=4)
    srv.warmup(p)
    srv.request(AllocRequest("t0", p, _caps(p, 2)))
    obs.gauge("market.demo.cost_regret", 1.25)
    snap = obs.snapshot()
    assert snap["counters"]["lp.newton.calls"] >= 1
    assert snap["counters"]["serving.requests"] >= 1
    assert snap["gauges"]["market.demo.cost_regret"] == 1.25
    assert "serving.queue_wait_s" in snap["histograms"]
    assert any(ev["kind"] in ("stacked", "compact")
               for ev in snap["compile_events"])
    assert snap["histograms"]["lp.newton.iters"]["count"] >= 1


def test_threaded_serving_episode_exports_nested_trace(tmp_path):
    """Acceptance: a threaded serving episode under ``obs.enabled()``
    exports a Chrome trace with nested dispatch spans and per-request
    lifecycle spans carrying the queue-wait/solve/slice breakdown."""
    p = _problem(0)
    srv = AllocationServer(ladder_max=8)
    srv.warmup(p)
    obs.enable()
    results = {}

    def tenant(i):
        req = AllocRequest(f"t{i}", p, _caps(p, 1 + i % 3))
        results[i] = srv.submit(req).result(timeout=60)

    with srv:
        threads = [threading.Thread(target=tenant, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    obs.disable()
    assert len(results) == 6
    for r in results.values():
        assert r.latency_s >= r.queue_wait_s >= 0
        assert r.solve_s > 0 and r.slice_s >= 0

    names = [e.name for e in obs.trace_events()]
    for expected in ("serving.dispatch", "serving.admit", "serving.solve",
                     "serving.slice", "serving.resolve", "serving.request",
                     "lp.solve_stacked"):
        assert expected in names, f"missing span {expected}"
    # request lifecycles carry the latency breakdown
    reqs = [e for e in obs.trace_events() if e.name == "serving.request"]
    assert len(reqs) == 6
    for ev in reqs:
        assert {"tenant", "queue_wait_ms", "solve_ms",
                "slice_ms"} <= set(ev.attrs)
    # nesting: every solve span sits inside some dispatch span
    evs = obs.trace_events()
    dispatches = [e for e in evs if e.name == "serving.dispatch"]
    for s in (e for e in evs if e.name == "serving.solve"):
        assert any(d.ts_ns <= s.ts_ns
                   and s.ts_ns + s.dur_ns <= d.ts_ns + d.dur_ns
                   for d in dispatches)
        assert s.depth > 0

    path = tmp_path / "serving_trace.json"
    n = obs.export_chrome_trace(str(path))
    doc = json.loads(path.read_text())
    assert len(doc["traceEvents"]) == n > 0
    ts = [e["ts"] for e in doc["traceEvents"]]
    assert ts == sorted(ts)
    assert all(e["ph"] == "X" for e in doc["traceEvents"])
