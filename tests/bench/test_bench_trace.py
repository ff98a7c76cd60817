"""The trace reduction: busy time as the union of op intervals, idle gaps
named by the host span open in them, device time per module; on
hand-made intervals and on a small trace recorded on the CPU."""
from __future__ import annotations

import time

import numpy as np
import pytest

from bench import devtrace as trace


def _summary():
    ms = 1_000_000
    ops = [trace.Op("a", "jit_f", "d0", 0 * ms, 10 * ms),
           trace.Op("b", "jit_f", "d0", 5 * ms, 15 * ms),
           trace.Op("c", "jit_g", "d0", 40 * ms, 50 * ms)]
    trace._set_self_times(ops)
    busy = {"d0": trace._merge([(o.start, o.end) for o in ops])}
    host = {trace.WINDOW: [(0, 100 * ms, 0)],
            "host.plan": [(15 * ms, 40 * ms, 0)],
            "host.inner": [(20 * ms, 30 * ms, 1)]}
    return trace.Summary((0, 100 * ms), ops, busy, host)


def test_busy_is_the_union_of_op_intervals():
    s = _summary()
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx(0.025)
    assert s.busy_within([(0, 12_000_000)]) == pytest.approx(0.012)


def test_idle_gaps_are_named_by_the_innermost_open_span():
    gaps = _summary().idle_gaps(3)
    assert [g[0] for g in gaps] == ["host:no span", "host.inner",
                                    "host:no span"] or \
        [round(g[1], 3) for g in gaps] == [0.05, 0.025]
    assert gaps[0][1] == pytest.approx(0.05)       # 50..100 ms, no span
    assert gaps[1] == ["host.inner", pytest.approx(0.025)]


def test_self_time_excludes_nested_ops():
    ms = 1_000_000
    ops = [trace.Op("loop", "m", "d", 0, 10 * ms),
           trace.Op("body", "m", "d", 2 * ms, 5 * ms),
           trace.Op("body", "m", "d", 6 * ms, 8 * ms)]
    trace._set_self_times(ops)
    assert [o.self_ns for o in ops] == [5 * ms, 3 * ms, 2 * ms]


def test_module_seconds_and_top_ops():
    s = _summary()
    mods = s.module_seconds()
    assert mods["jit_g"] == pytest.approx(0.01)
    assert mods["jit_f"] == pytest.approx(0.02)
    assert s.top_ops(1)[0][0] in ("jit_f/a", "jit_f/b", "jit_g/c")


def test_cpu_trace_reduces(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    @jax.jit
    def f(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    t0 = time.perf_counter()
    with TraceAnnotation(trace.WINDOW):
        for _ in range(3):
            f(x).block_until_ready()
            with TraceAnnotation("host.pause"):
                time.sleep(0.05)
    wall = time.perf_counter() - t0
    jax.profiler.stop_trace()
    s = trace.reduce(tmp_path, {"host.pause"})
    # the window ends with the last device op: the last pause is cut off
    assert wall - 0.1 <= s.window_s <= wall
    assert 0 < s.busy_s < s.window_s
    own = [o for o in s.ops if o.module == "jit_f"]
    assert own and s.module_seconds()["jit_f"] > 0
    # the ops' own union never exceeds their summed own time
    assert s.busy_s <= sum(o.end - o.start for o in s.ops) * 1e-9 + 1e-9
    longest = s.idle_gaps(1)[0]
    assert longest[0] == "host.pause"
    assert longest[1] == pytest.approx(0.05, abs=0.03)
    assert np.isfinite(s.busy_within(
        (a, b) for a, b, _ in s.host["host.pause"]))
