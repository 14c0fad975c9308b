"""The trace reduction, on synthetic intervals and on a small trace that
the test records on the CPU."""
import time

import numpy as np
import pytest

from chipbench import trace as tracing


def test_union_merges_overlaps_and_touching():
    got = tracing.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 11)])
    assert got == [(0, 4), (5, 7), (10, 11)]


def test_gaps_are_the_complement_inside_the_window():
    busy = tracing.union([(2, 4), (6, 8)])
    assert tracing.gaps(busy, 0, 10) == [(0, 2), (4, 6), (8, 10)]
    assert tracing.gaps(tracing.clip(busy, 3, 7), 3, 7) == [(4, 6)]


def test_gap_goes_to_the_span_that_overlaps_it_most():
    spans = [("submit", 0, 5), ("frontend.step", 4, 20)]
    assert tracing.attribute((3, 9), spans) == "frontend.step"
    assert tracing.attribute((30, 40), spans) == "no span"


def test_reduce_synthetic_two_devices():
    ev = tracing.TraceEvents(
        ops={"/device:TPU:0": [("a", 0, 4e9), ("b", 2e9, 6e9)],
             "/device:TPU:1": [("a", 0, 2e9)]},
        modules={"/device:TPU:0": [("jit__decode_sel_core", 0, 6e9)],
                 "/device:TPU:1": [("jit__decode_sel_core", 0, 2e9)]},
        spans=[("reference_check", 6e9, 10e9)], window=(0, 10e9))
    red = tracing.reduce(ev)
    assert red.window_s == pytest.approx(10.0)
    assert red.busy_s == pytest.approx((6 + 2) / 2)     # mean over devices
    assert red.idle_share == pytest.approx(0.6)
    assert red.module_seconds("_decode_sel_core") == pytest.approx(8.0)
    assert red.idle_gaps == [("reference_check", pytest.approx(8.0)),
                             ("reference_check", pytest.approx(4.0))]
    assert red.breakdown()["device_ops"][0][0] == "jit__decode_sel_core"


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((384, 384), jnp.float32)
    f(x).block_until_ready()
    d = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(d))
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("frontend.step"):
                f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("reference_check"):
            time.sleep(0.08)
        with jax.profiler.TraceAnnotation("frontend.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    return tracing.load(tracing.latest_xplane(str(d)))


def test_recorded_trace_busy_is_the_union_of_ops(cpu_trace):
    red = tracing.reduce(cpu_trace)
    lo, hi = cpu_trace.window
    assert red.window_s == pytest.approx((hi - lo) * 1e-9)
    # independent union: mark a microsecond timeline
    ops = [(s, e) for v in cpu_trace.ops.values() for _, s, e in v]
    t = np.zeros(int((hi - lo) / 1e3) + 1, bool)
    for s, e in ops:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            t[int((a - lo) / 1e3):int(np.ceil((b - lo) / 1e3))] = True
    assert red.busy_s == pytest.approx(t.sum() * 1e-6, rel=0.05, abs=2e-4)
    assert 0.0 < red.busy_s < red.window_s
    assert red.idle_share == pytest.approx(1 - red.busy_s / red.window_s)


def test_recorded_trace_per_executable_time(cpu_trace):
    red = tracing.reduce(cpu_trace)
    assert red.module_seconds("<lambda>") > 0
    assert red.module_seconds("<lambda>") <= red.window_s


def test_recorded_trace_gap_attributed_to_host_span(cpu_trace):
    red = tracing.reduce(cpu_trace)
    name, seconds = red.idle_gaps[0]
    assert name == "reference_check"
    assert seconds >= 0.07
