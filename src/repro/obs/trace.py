"""Thread-safe span tracing with Chrome-trace and JSONL exporters.

A span is one timed region of one thread: ``with obs.span("name",
key=val): ...`` records a monotonic-clock interval plus free-form
attributes.  Nesting is per-thread (a thread-local depth counter), so
concurrent scheduler / submitter threads interleave without locking on
the hot path — only the final append of a COMPLETED span takes the
collector lock.

Tracing is **off by default** and ``span()`` is then a strict no-op: it
returns a shared singleton context manager without touching the
collector, so instrumented hot paths (the stacked-IPM chunk loop, the
serving dispatch path) pay one function call and one flag test.  The
overhead bound is asserted by the ``obs.overhead`` row of
``benchmarks/obs_bench.py`` and by ``tests/test_obs.py``.

Exporters:

* :func:`export_chrome_trace` — Chrome trace-event JSON ("X" complete
  events, microsecond timestamps) loadable in Perfetto
  (https://ui.perfetto.dev) or ``chrome://tracing``;
* :func:`export_jsonl` — one JSON object per span per line, for ad-hoc
  ``jq``/pandas analysis.

See docs/observability.md for the full contract.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import List, NamedTuple, Optional


class SpanEvent(NamedTuple):
    """One completed span (times in ns on the perf_counter clock)."""
    name: str
    ts_ns: int
    dur_ns: int
    tid: int
    depth: int
    attrs: Optional[dict]


class _TraceState:
    def __init__(self):
        self.lock = threading.Lock()
        self.events: List[SpanEvent] = []
        self.local = threading.local()
        # jax.profiler.TraceAnnotation while spans are mirrored, else None
        self.annotation = None


_STATE = _TraceState()
# module-level flag: the one attribute ``span()`` reads on the disabled
# fast path (kept out of _STATE so the lookup is a plain global load)
_ENABLED = False


def enabled() -> bool:
    """True while span tracing is on (see :func:`enable`)."""
    return _ENABLED


def enable(*, reset: bool = True, jax_profiler: bool = False) -> None:
    """Turn span tracing on.

    ``reset`` drops previously collected spans (default — each capture
    is self-contained).  ``jax_profiler=True`` additionally mirrors
    every span into a ``jax.profiler.TraceAnnotation`` named scope, so
    host spans line up with device activity in a ``jax.profiler`` trace
    (used by the benchmark drivers' ``--profile-dir`` flag).
    """
    global _ENABLED
    annotation = None
    if jax_profiler:
        from jax.profiler import TraceAnnotation as annotation
    with _STATE.lock:
        if reset:
            _STATE.events.clear()
        _STATE.annotation = annotation
    _ENABLED = True


def disable() -> None:
    """Turn span tracing off (collected spans are kept for export)."""
    global _ENABLED
    _ENABLED = False
    _STATE.annotation = None


class capture:
    """Context manager: trace spans for the duration of a block.

    ``with obs.capture() as events: ...`` — ``events`` is the live list
    snapshot accessor; read :func:`trace_events` after the block.
    """

    def __init__(self, **enable_kw):
        self._kw = enable_kw

    def __enter__(self):
        enable(**self._kw)
        return trace_events

    def __exit__(self, *exc):
        disable()
        return False


def _depth() -> int:
    return getattr(_STATE.local, "depth", 0)


class _Span:
    """A live (enabled-mode) span.  ``set(**attrs)`` adds attributes
    any time before exit (e.g. a result computed mid-block)."""

    __slots__ = ("name", "attrs", "_t0", "_depth", "_ann")

    def __init__(self, name: str, attrs: Optional[dict]):
        self.name = name
        self.attrs = attrs
        self._ann = None

    def set(self, **attrs) -> "_Span":
        if self.attrs is None:
            self.attrs = attrs
        else:
            self.attrs.update(attrs)
        return self

    def __enter__(self) -> "_Span":
        annotation = _STATE.annotation
        if annotation is not None:
            self._ann = annotation(self.name)
            self._ann.__enter__()
        self._depth = _depth()
        _STATE.local.depth = self._depth + 1
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        _STATE.local.depth = self._depth
        if self._ann is not None:
            self._ann.__exit__(*exc)
        ev = SpanEvent(self.name, self._t0, t1 - self._t0,
                       threading.get_ident(), self._depth, self.attrs)
        with _STATE.lock:
            _STATE.events.append(ev)
        return False


class _NoopSpan:
    """Shared do-nothing span: the disabled fast path.  Stateless, so
    one singleton serves every thread concurrently."""

    __slots__ = ()

    def set(self, **attrs) -> "_NoopSpan":
        return self

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


def span(name: str, **attrs):
    """Context manager timing one region of the current thread.

    Disabled (the default): returns a shared no-op singleton — no event,
    no collector access, no retained allocation.  Enabled: records a
    :class:`SpanEvent` with monotonic start/duration, thread id, the
    per-thread nesting depth, and ``attrs``.
    """
    if not _ENABLED:
        return _NOOP
    return _Span(name, attrs or None)


def add_span(name: str, t0_ns: int, t1_ns: int, **attrs) -> None:
    """Record an explicit span from externally measured timestamps
    (``time.perf_counter_ns``) — for lifecycles that start and end on
    different threads, e.g. a serving request's submit→resolve window.
    No-op while tracing is disabled."""
    if not _ENABLED:
        return
    ev = SpanEvent(name, int(t0_ns), max(int(t1_ns) - int(t0_ns), 0),
                   threading.get_ident(), _depth(), attrs or None)
    with _STATE.lock:
        _STATE.events.append(ev)


def trace_events() -> List[SpanEvent]:
    """Snapshot (copy) of the collected spans, in completion order."""
    with _STATE.lock:
        return list(_STATE.events)


def clear_trace() -> None:
    with _STATE.lock:
        _STATE.events.clear()


def drop_events(name: str) -> int:
    """Remove collected spans with this name — e.g. calibration spans a
    benchmark recorded while an outer driver was tracing.  Returns the
    number removed."""
    with _STATE.lock:
        before = len(_STATE.events)
        _STATE.events[:] = [e for e in _STATE.events if e.name != name]
        return before - len(_STATE.events)


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------

def _chrome_events(events: List[SpanEvent]) -> List[dict]:
    pid = os.getpid()
    # compact thread ids: Chrome renders one lane per tid; stable small
    # ints keep the lanes readable across exports
    tids: dict = {}
    out = []
    for ev in sorted(events, key=lambda e: (e.ts_ns, -e.dur_ns)):
        tid = tids.setdefault(ev.tid, len(tids))
        rec = {"name": ev.name, "ph": "X", "pid": pid, "tid": tid,
               "ts": ev.ts_ns / 1e3, "dur": ev.dur_ns / 1e3}
        if ev.attrs:
            rec["args"] = {k: _jsonable(v) for k, v in ev.attrs.items()}
        out.append(rec)
    return out


def _jsonable(v):
    try:
        json.dumps(v)
        return v
    except TypeError:
        return repr(v)


def export_chrome_trace(path: str, events=None) -> int:
    """Write the collected spans as Chrome trace-event JSON ("X"
    complete events, microsecond units, sorted by start time).  Open in
    Perfetto (ui.perfetto.dev) or ``chrome://tracing``.  Returns the
    number of events written."""
    events = trace_events() if events is None else list(events)
    payload = {"traceEvents": _chrome_events(events),
               "displayTimeUnit": "ms"}
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    return len(events)


def export_jsonl(path: str, events=None) -> int:
    """Write one JSON object per span per line (``ts_us`` / ``dur_us``
    microseconds, plus name, tid, depth and the span attrs).  Returns
    the number of events written."""
    events = trace_events() if events is None else list(events)
    with open(path, "w") as f:
        for ev in sorted(events, key=lambda e: e.ts_ns):
            rec = {"name": ev.name, "ts_us": ev.ts_ns / 1e3,
                   "dur_us": ev.dur_ns / 1e3, "tid": ev.tid,
                   "depth": ev.depth}
            if ev.attrs:
                rec["args"] = {k: _jsonable(v) for k, v in ev.attrs.items()}
            f.write(json.dumps(rec) + "\n")
    return len(events)
