"""One thread-safe metrics registry: counters, gauges, histograms,
with generic stack-based scoping.

Unlike span tracing (off by default), the registry is ALWAYS on — it is
the substrate the solver's Newton-row ledger, the B&B round counters
and the serving layer's latency breakdown all write to, and those
consumers rely on counts being there after the fact.  Every
mutation takes one lock, so concurrent writers (the
``AllocationServer`` scheduler thread next to benchmark/main threads)
never lose updates — the failure mode the old module-level
``lp._NEWTON_STATS`` dict had.

Scoping replaces the hand-rolled save/restore dance the old
``lp.newton_ledger`` played: ``with obs.scope() as scoped: ...`` pushes
a fresh frame; writes inside the block land in that frame, reads
(:func:`read_counter` etc.) see the innermost frame, and on exit the
frame's contents are merged into the parent so an outer scope still
sees everything.  ``scoped`` is filled with the frame's data at exit.

:func:`snapshot` aggregates ACROSS all live frames — one structured
view of everything recorded so far (counters, gauges, histogram
summaries), regardless of scope nesting.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, List, Optional


class _Frame:
    __slots__ = ("counters", "gauges", "hists")

    def __init__(self):
        self.counters: Dict[str, float] = {}
        self.gauges: Dict[str, float] = {}
        self.hists: Dict[str, List[float]] = {}


def _percentile(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank percentile of an already-sorted sample list."""
    if not sorted_vals:
        return 0.0
    i = min(int(q / 100.0 * len(sorted_vals)), len(sorted_vals) - 1)
    return sorted_vals[i]


class MetricsRegistry:
    """Counters / gauges / histograms behind one lock, with scoping."""

    def __init__(self):
        self._lock = threading.RLock()
        self._frames: List[_Frame] = [_Frame()]

    # -- writes --------------------------------------------------------

    def inc(self, name: str, value: float = 1) -> None:
        """Add ``value`` to counter ``name`` (created at 0)."""
        with self._lock:
            c = self._frames[-1].counters
            c[name] = c.get(name, 0) + value

    def gauge(self, name: str, value: float) -> None:
        """Set gauge ``name`` (last-write-wins)."""
        with self._lock:
            self._frames[-1].gauges[name] = value

    def observe(self, name: str, value: float) -> None:
        """Record one histogram sample."""
        with self._lock:
            self._frames[-1].hists.setdefault(name, []).append(float(value))

    def observe_many(self, name: str, values) -> None:
        with self._lock:
            self._frames[-1].hists.setdefault(name, []).extend(
                float(v) for v in values)

    def update(self, counters: Optional[dict] = None,
               observations: Optional[dict] = None) -> None:
        """Atomically apply a batch of counter increments and histogram
        samples (``observations`` maps name -> iterable of samples) —
        one lock acquisition for a whole ledger record."""
        with self._lock:
            frame = self._frames[-1]
            if counters:
                for k, v in counters.items():
                    frame.counters[k] = frame.counters.get(k, 0) + v
            if observations:
                for k, vals in observations.items():
                    frame.hists.setdefault(k, []).extend(
                        float(v) for v in vals)

    # -- reads (innermost frame: what the current scope recorded) ------

    def read_counter(self, name: str, default: float = 0) -> float:
        with self._lock:
            return self._frames[-1].counters.get(name, default)

    def read_counters(self, prefix: str = "") -> Dict[str, float]:
        with self._lock:
            return {k: v for k, v in self._frames[-1].counters.items()
                    if k.startswith(prefix)}

    def read_hist(self, name: str) -> List[float]:
        with self._lock:
            return list(self._frames[-1].hists.get(name, ()))

    def reset(self, prefix: str = "") -> None:
        """Drop the innermost frame's entries under ``prefix`` (all of
        them with the default empty prefix).  Outer scopes keep their
        accumulations."""
        with self._lock:
            frame = self._frames[-1]
            for store in (frame.counters, frame.gauges, frame.hists):
                for k in [k for k in store if k.startswith(prefix)]:
                    del store[k]

    # -- scoping -------------------------------------------------------

    @contextlib.contextmanager
    def scope(self):
        """Push a fresh frame: writes inside the block accumulate from
        zero, reads see only the block's own activity, and on exit the
        frame merges into the parent.  Yields a dict that is filled
        with the frame's ``counters`` / ``gauges`` / ``histograms`` at
        exit."""
        with self._lock:
            self._frames.append(_Frame())
        out: dict = {}
        try:
            yield out
        finally:
            with self._lock:
                frame = self._frames.pop()
                out["counters"] = dict(frame.counters)
                out["gauges"] = dict(frame.gauges)
                out["histograms"] = {k: list(v)
                                     for k, v in frame.hists.items()}
                parent = self._frames[-1]
                for k, v in frame.counters.items():
                    parent.counters[k] = parent.counters.get(k, 0) + v
                parent.gauges.update(frame.gauges)
                for k, vals in frame.hists.items():
                    parent.hists.setdefault(k, []).extend(vals)

    # -- snapshot ------------------------------------------------------

    def snapshot(self) -> dict:
        """One structured view across ALL frames: summed counters,
        innermost-wins gauges, and per-histogram summaries
        (count/mean/min/max/p50/p99)."""
        with self._lock:
            counters: Dict[str, float] = {}
            gauges: Dict[str, float] = {}
            hists: Dict[str, List[float]] = {}
            for frame in self._frames:
                for k, v in frame.counters.items():
                    counters[k] = counters.get(k, 0) + v
                gauges.update(frame.gauges)
                for k, vals in frame.hists.items():
                    hists.setdefault(k, []).extend(vals)
        summaries = {}
        for k, vals in hists.items():
            s = sorted(vals)
            summaries[k] = {
                "count": len(s),
                "mean": sum(s) / len(s) if s else 0.0,
                "min": s[0] if s else 0.0,
                "max": s[-1] if s else 0.0,
                "p50": _percentile(s, 50),
                "p99": _percentile(s, 99),
            }
        return {"counters": counters, "gauges": gauges,
                "histograms": summaries}


REGISTRY = MetricsRegistry()
