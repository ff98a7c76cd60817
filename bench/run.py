#!/usr/bin/env python3
"""The benchmark's one command.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of the machine it is
started on: makes the cell's inputs from the seed, warms up every shape
the cell uses (all of it counted as set-up), measures for ``--seconds``,
then checks what the timed path produced against the plain reference in
``bench/reference``.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and with ``--trace 1`` a ``breakdown``), then ``checks``, each number
compared beside its limit.  The same numbers are the last lines of
standard error.

Everything belonging to one cell is found by name: the configuration in
``bench/configs/<config>.json``, the traffic mix in
``bench/traffic/<traffic>.json`` (whose ``driver`` names the generator
in ``bench/drivers/``), each per-layer metric in
``bench/metrics/<metric>.py``.  ``--trace 0`` prints the cell's
end-to-end metrics; ``--trace 1`` profiles the window and prints its
per-layer metrics.

The run exits non-zero, printing no result, when JAX finds no TPU or
fewer chips than the cell asks for.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Optional

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# The manifest and the files it names
# ---------------------------------------------------------------------------

def load_manifest(root: Path) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list          # manifest entries this cell reports
    per_layer: list


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(root: Path, name: str) -> Cell:
    from bench import data
    man = load_manifest(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench/run.py: no workload {name!r} in "
                         f"BENCHMARK.json (have {sorted(cells)})")
    w = cells[name]
    mix_path = Path(root) / "bench" / "traffic" / f"{w['traffic']}.json"
    mix = json.loads(mix_path.read_text())
    return Cell(name, int(w["chips"]), data.load_config(root, w["config"]),
                mix, [m for m in man["end_to_end"] if reports(m, name)],
                [m for m in man["per_layer"] if reports(m, name)])


def load_driver(name: str):
    return importlib.import_module(f"bench.drivers.{name}")


def load_metric(root: Path, name: str):
    """The reader module ``bench/metrics/<name>.py``."""
    path = Path(root) / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench.metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Observed:
    """What a per-layer reader may read: the program's spans and counter
    deltas of the window, the reduced device trace, the driver's own
    samples, and the (start, end) ns intervals in which the run itself
    started or stopped the profiler."""
    spans: list
    counters: dict
    hists: dict
    trace: object
    raw: dict
    profiler_ns: list = dataclasses.field(default_factory=list)


# ---------------------------------------------------------------------------
# Device and compile cache
# ---------------------------------------------------------------------------

def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else ``<checkout>/.jax_cache`` (a fixed path: the directory is
    part of the cache key)."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(Path(root) / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def chips_or_exit(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench/run.py: no TPU found; JAX's devices are "
                         f"{len(devs)} x {devs[0].platform!r}, and this "
                         f"benchmark measures only on a TPU")
    if len(devs) < chips:
        raise SystemExit(f"bench/run.py: the cell asks for {chips} TPU "
                         f"chips, JAX finds {len(devs)}")
    return devs[:chips]


def device_peaks(root: Path, kind: str) -> dict:
    """The published peaks of one chip of ``kind`` (``bench/peaks.json``);
    a device that is not in the table is an error."""
    table = json.loads((Path(root) / "bench" / "peaks.json").read_text())
    if kind not in table or kind == "source":
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json")
    return table[kind]


class Profile:
    """The traced slice of a ``--trace 1`` run: ``jax.profiler`` running,
    with the ``bench.window`` annotation open, from :meth:`start` to
    :meth:`stop` (each acts once).  The run starts it with its window,
    unless the cell's driver has a ``trace_slice`` of its own: then the
    driver starts and stops it at points inside the window, and the run
    stops it when the window closes."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.started = self.stopped = False
        self._window = None
        # (start, end) ns on the perf_counter clock of each start and stop:
        # the profiler's own host time, which readers leave out of spans
        self.own_ns = []

    def start(self) -> None:
        if self.started:
            return
        t0 = time.perf_counter_ns()
        import jax
        from jax.profiler import TraceAnnotation
        from bench import devtrace
        # no Python call tracing: it would slow the host it measures
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._window = TraceAnnotation(devtrace.WINDOW)
        self._window.__enter__()
        self.started = True
        self.own_ns.append((t0, time.perf_counter_ns()))

    def stop(self) -> None:
        if not self.started or self.stopped:
            return
        t0 = time.perf_counter_ns()
        import jax
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.stopped = True
        self.own_ns.append((t0, time.perf_counter_ns()))


class _CompileCounter:
    """Counts XLA backend compiles while armed (a compile inside the
    measured window is a fault of the warm-up)."""

    def __init__(self):
        self.armed = False
        self.count = 0
        try:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(self._on)
        except (ImportError, AttributeError):
            pass

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def execute(root: Path, workload: str, seed: int, seconds: float,
            trace: bool, log=None) -> dict:
    """Run one cell and return the result object (the last stdout
    line)."""
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    cell = find_cell(root, workload)
    enable_compile_cache(root)
    devs = chips_or_exit(cell.chips)
    device_peaks(root, devs[0].device_kind)
    log(f"{len(devs)} x {devs[0].device_kind} at "
        f"{time.perf_counter() - T_START:.3f} s")
    from repro import obs
    driver = load_driver(cell.mix["driver"])
    compiles = _CompileCounter()
    if trace:
        # a traced run profiles a short slice: the device trace of a long
        # window outgrows what the profiler keeps
        seconds = min(seconds, float(cell.mix["trace_seconds"]))
    state = driver.setup(cell.config, cell.mix, seed, seconds, root=root,
                         log=log)
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f} s; window {seconds} s")

    prof = None
    counters0 = obs.read_counters()
    hists0 = {h: len(obs.read_hist(h)) for h in cell.mix.get("hists", ())}
    if trace:
        prof = Profile()
        obs.enable(jax_profiler=True)
        if hasattr(driver, "trace_slice"):
            driver.trace_slice(state, prof)
        else:
            prof.start()
    compiles.armed = True
    try:
        raw = driver.window(state, seconds)
    finally:
        compiles.armed = False
        if trace:
            prof.stop()
            obs.disable()
    counters1 = obs.read_counters()
    counters = {k: v - counters0.get(k, 0) for k, v in counters1.items()}
    hists = {h: obs.read_hist(h)[n0:] for h, n0 in hists0.items()}
    spans = obs.trace_events() if trace else []
    log(f"window done: {raw['attempted']} attempted, {raw['failed']} "
        f"failed, {compiles.count} compiles inside the window")

    mem = [d.memory_stats() or {} for d in devs]
    peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}

    metrics, breakdown = {}, None
    if trace:
        from bench import devtrace
        summary = devtrace.reduce(prof.dir, {s.name for s in spans})
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = {"device_ops": summary.top_ops(10),
                     "idle_gaps": summary.idle_gaps(10)}
        seen = Observed(spans, counters, hists, summary, raw, prof.own_ns)
        for m in cell.per_layer:
            value = load_metric(root, m["name"]).read(seen)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        if summary.ops:
            w0 = summary.window[0]
            log(f"trace: {len(summary.ops)} device ops from "
                f"{(min(o.start for o in summary.ops) - w0) * 1e-9:.3f} s "
                f"to {(max(o.end for o in summary.ops) - w0) * 1e-9:.3f} s "
                f"of the {summary.window_s:.3f} s traced window")
        else:
            log("trace: no device op found; planes were "
                + devtrace.describe(prof.dir))
        shutil.rmtree(prof.dir, ignore_errors=True)
    else:
        e2e = dict(driver.end_to_end(raw), setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}

    driver.release(state)
    checks = driver.check(state, raw, seed, log=log)
    correct = bool(checks) and all(c["value"] <= c["limit"]
                                   for c in checks.values())
    result = {"correct": correct, "attempted": int(raw["attempted"]),
              "failed": int(raw["failed"]), "metrics": metrics,
              "device": device, "compiles_in_window": compiles.count}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    result = execute(ROOT, args.workload, args.seed, args.seconds,
                     bool(args.trace))
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
