"""Reduce a ``jax.profiler`` trace to device busy time, idle gaps and
per-module and per-op device time.

Device operations are read from each ``/device:*`` plane's ``XLA Ops``
line (with the ``XLA Modules`` line naming the program each op ran in);
on the CPU backend, where there is no device plane, from host events
that carry an ``hlo_op`` stat.  Host spans are the ``TraceAnnotation``
events the benchmark and the program's ``obs`` spans write; they share
the trace's clock with the device, which is what lets an idle gap be
put down to what the host was doing.

The traced window runs from the benchmark's ``bench.window`` span to its
end or to the end of the last device op the profiler kept, whichever
comes first: the TPU profiler keeps a fixed number of device events
(about 6.3 million, some 1.5 s of the stacked interior point) and drops
later ones.  Busy time is the union of op intervals per device, averaged
over the devices that ran anything; an op's own time excludes ops nested inside
it on the same line, so per-op totals do not count a loop and its body
twice.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW = "bench.window"       # host span the run opens around its window
_MODULE_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Op:
    name: str
    module: str
    device: str
    start: int                 # ns, trace clock
    end: int
    self_ns: int = 0


@dataclasses.dataclass
class Summary:
    window: Tuple[int, int]                  # ns, trace clock
    ops: List[Op]
    busy: Dict[str, List[Tuple[int, int]]]   # device -> merged intervals
    host: Dict[str, List[Tuple[int, int, int]]]  # span -> (start, end, depth)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in the window in which an op ran, averaged over the
        devices that ran any."""
        per = [_overlap(iv, [self.window]) for iv in self.busy.values()]
        return sum(per) / len(per) * 1e-9 if per else 0.0

    def busy_within(self, spans: Iterable[Tuple[int, int]]) -> float:
        """Device-busy seconds inside the given intervals (averaged over
        devices)."""
        spans = _merge(list(spans))
        per = [_overlap(iv, spans) for iv in self.busy.values()]
        return sum(per) / len(per) * 1e-9 if per else 0.0

    def module_seconds(self) -> Dict[str, float]:
        """Device seconds per program (module), ops in the window, own
        time."""
        out: Dict[str, float] = {}
        for op in self._in_window():
            out[op.module] = out.get(op.module, 0.0) + op.self_ns * 1e-9
        return out

    def top_ops(self, k: int = 10) -> List[list]:
        per: Dict[str, float] = {}
        for op in self._in_window():
            key = f"{op.module}/{op.name}"
            per[key] = per.get(key, 0.0) + op.self_ns * 1e-9
        return [[n, s] for n, s in sorted(per.items(),
                                          key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10) -> List[list]:
        """The ``k`` longest idle gaps of the window, each named by the
        innermost host span open at its midpoint."""
        gaps = []
        for iv in self.busy.values() or [[]]:
            t = self.window[0]
            for s, e in iv + [(self.window[1], self.window[1])]:
                s, e = max(s, self.window[0]), min(e, self.window[1])
                if s > t:
                    gaps.append((t, s))
                t = max(t, e)
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.host_at((a + b) // 2), (b - a) * 1e-9]
                for a, b in gaps[:k]]

    def host_at(self, t: int) -> str:
        best, key = "host:no span", None
        for name, ivs in self.host.items():
            if name == WINDOW:
                continue
            for s, e, depth in ivs:
                if s <= t < e and (key is None or (depth, s) > key):
                    best, key = name, (depth, s)
        return best

    def _in_window(self):
        a, b = self.window
        return [op for op in self.ops if a <= op.start < b]


def _merge(iv: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _overlap(a: List[Tuple[int, int]], b: List[Tuple[int, int]]) -> int:
    """Length of the intersection of two merged interval lists."""
    total, j = 0, 0
    starts = [s for s, _ in b]
    for s, e in a:
        j = max(0, bisect.bisect_right(starts, s) - 1)
        while j < len(b) and b[j][0] < e:
            total += max(0, min(e, b[j][1]) - max(s, b[j][0]))
            j += 1
    return total


def _set_self_times(ops: List[Op]) -> None:
    """Own time of each op: its duration less the ops nested in it (ops
    of one device and line are passed together, sorted by start)."""
    stack: List[Op] = []
    for op in ops:
        op.self_ns = op.end - op.start
        while stack and stack[-1].end <= op.start:
            stack.pop()
        if stack and op.end <= stack[-1].end:
            stack[-1].self_ns -= op.end - op.start
        stack.append(op)


def reduce(profile_dir, host_names: Optional[set] = None) -> Summary:
    """Read the one ``.xplane.pb`` under ``profile_dir`` and reduce it."""
    from jax.profiler import ProfileData
    files = sorted(Path(profile_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    names = set(host_names or ()) | {WINDOW}
    ops: List[Op] = []
    host: Dict[str, List[Tuple[int, int, int]]] = {}
    for plane in pd.planes:
        lines = list(plane.lines)
        if plane.name.startswith("/device:"):
            by_name = {ln.name: ln for ln in lines}
            if "XLA Ops" not in by_name:
                continue
            modules = sorted(
                (int(e.start_ns), int(e.start_ns + e.duration_ns),
                 _MODULE_SUFFIX.sub("", e.name))
                for e in (by_name["XLA Modules"].events
                          if "XLA Modules" in by_name else ()))
            starts = [m[0] for m in modules]
            line_ops = []
            for e in by_name["XLA Ops"].events:
                s = int(e.start_ns)
                i = bisect.bisect_right(starts, s) - 1
                mod = modules[i][2] if i >= 0 and s < modules[i][1] else "?"
                # a TPU op's event name is its HLO text: keep the op's name
                name = e.name.split(" = ")[0].lstrip("%")
                line_ops.append(Op(name, mod, plane.name, s,
                                   s + int(e.duration_ns)))
            line_ops.sort(key=lambda o: (o.start, -o.end))
            _set_self_times(line_ops)
            ops.extend(line_ops)
            continue
        for ln in lines:
            line_ops = []
            stack: List[int] = []
            evs = sorted(ln.events, key=lambda e: (e.start_ns,
                                                   -e.duration_ns))
            for e in evs:
                s, d = int(e.start_ns), int(e.duration_ns)
                stats = dict(e.stats)
                if "hlo_op" in stats:
                    dev = f"{plane.name}:{stats.get('device_ordinal', 0)}"
                    line_ops.append(Op(str(stats["hlo_op"]),
                                       str(stats.get("hlo_module", "?")),
                                       dev, s, s + d))
                elif e.name in names:
                    while stack and stack[-1] <= s:
                        stack.pop()
                    host.setdefault(e.name, []).append((s, s + d,
                                                        len(stack)))
                    stack.append(s + d)
            line_ops.sort(key=lambda o: (o.start, -o.end))
            _set_self_times(line_ops)
            ops.extend(line_ops)
    busy: Dict[str, List[Tuple[int, int]]] = {}
    for op in ops:
        busy.setdefault(op.device, []).append((op.start, op.end))
    busy = {d: _merge(iv) for d, iv in busy.items()}
    if WINDOW in host:
        w = max(host[WINDOW], key=lambda iv: iv[1] - iv[0])[:2]
    else:
        allt = [t for iv in busy.values() for p in iv for t in p]
        w = (min(allt), max(allt)) if allt else (0, 0)
    if ops:
        # the profiler keeps a fixed number of device events and drops the
        # rest: the traced window ends where the device record does
        w = (w[0], max(w[0], min(w[1], max(op.end for op in ops))))
    return Summary(w, ops, busy, host)


def describe(profile_dir) -> str:
    """Planes, lines and event counts of a trace: what to look at when
    the reduction finds no device op."""
    from jax.profiler import ProfileData
    out = []
    for f in sorted(Path(profile_dir).rglob("*.xplane.pb")):
        for plane in ProfileData.from_file(str(f)).planes:
            lines = [f"{ln.name}:{sum(1 for _ in ln.events)}"
                     for ln in plane.lines]
            out.append(f"{plane.name} [{', '.join(lines[:12])}]")
    return "; ".join(out)
